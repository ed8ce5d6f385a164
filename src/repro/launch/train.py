"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \\
        --steps 200 --batch 8 --seq 128 --ckpt /tmp/ckpt

Runs on whatever devices the host has (CPU smoke / TPU slice), with the full
substrate engaged: sharded deterministic data pipeline, AdamW + cosine
schedule, remat, checkpoint/restart via the resilient runner, cross-pod
serdes gradient sync when the mesh has a pod axis.

``--metrics PATH`` turns on the telemetry metrics registry: wall-clock step
times land in the ``train.step.seconds`` histogram (p50/p99/p99.9 printed at
the end) and the per-step MoE NoC metrics publish under the shared
``noc.moe.*`` names; the JSON snapshot is written to PATH ('-' = stdout).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..checkpoint import CheckpointConfig, CheckpointManager
from ..configs import get_config
from ..data import DataConfig, ShardedTokenPipeline
from ..models import transformer as T
from ..models.layers import init_params
from ..optim import AdamWConfig, adamw_init
from ..runtime import FTConfig, ResilientRunner
from .cache import use_compile_cache
from .mesh import make_host_mesh
from .steps import make_train_step, shardings_for_params


def build_state(cfg, mesh, seed: int = 0):
    psh = shardings_for_params(cfg, mesh)
    specs = T.abstract_params(cfg)

    @jax.jit
    def init(key):
        return init_params(specs, key)

    with jax.set_mesh(mesh):
        params = jax.jit(init, out_shardings=psh)(jax.random.key(seed))
        opt = jax.jit(adamw_init, out_shardings=None)(params)
    return {"params": params, "opt": opt}


def run(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--pod-sync", default="auto", choices=["auto", "serdes"])
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="enable the telemetry metrics registry; write the "
                         "JSON snapshot here ('-' prints to stdout)")
    args = ap.parse_args(argv)
    use_compile_cache()

    reg = None
    if args.metrics:
        from ..telemetry.metrics import enable_metrics
        reg = enable_metrics()

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh(model=args.model_parallel)
    opt_cfg = AdamWConfig(lr=args.lr)
    step_fn = make_train_step(cfg, mesh, opt_cfg, pod_sync=args.pod_sync,
                              total_steps=args.steps, warmup=max(args.steps // 20, 5))

    state = build_state(cfg, mesh, args.seed)
    n_params = cfg.param_count()
    print(f"arch={cfg.name} params={n_params:,} mesh={dict(mesh.shape)} "
          f"tokens/step={args.batch * args.seq}")

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
                      seed=args.seed)
    pipeline = ShardedTokenPipeline(dcfg)

    with jax.set_mesh(mesh):
        jitted = jax.jit(step_fn, donate_argnums=(0,))
        losses = []

        def wrapped(state, batch):
            jb = {k: jnp.asarray(v) for k, v in batch.items()}
            if cfg.family == "encdec":
                jb["frames"] = jnp.zeros((args.batch, cfg.enc_seq, cfg.d_frontend),
                                         cfg.cdtype)
            if cfg.family == "vlm":
                jb["patches"] = jnp.zeros((args.batch, cfg.n_patches, cfg.d_frontend),
                                          cfg.cdtype)
            ts = time.perf_counter()
            state, mets = jitted(state, jb)
            loss = float(mets["loss"])   # blocks on the step's results
            if reg is not None:
                reg.histogram("train.step.seconds").observe(
                    time.perf_counter() - ts)
                reg.record_step_metrics(mets)
            losses.append(loss)
            n = len(losses)
            if n % args.log_every == 0 or n == 1:
                print(f"step {n:5d}  loss {losses[-1]:.4f}  "
                      f"gnorm {float(mets['grad_norm']):.3f}")
            return state

        if args.ckpt:
            cm = CheckpointManager(CheckpointConfig(args.ckpt, keep_last=2))
            runner = ResilientRunner(wrapped, cm,
                                     FTConfig(checkpoint_every=args.ckpt_every))
            start = cm.latest_step() or 0
            if start:
                state, start, _ = cm.restore(state)
                print(f"restored from step {start}")
            t0 = time.monotonic()
            state, stats = runner.run(state, pipeline, args.steps, start)
            dt = time.monotonic() - t0
        else:
            t0 = time.monotonic()
            for s in range(args.steps):
                state = wrapped(state, pipeline.batch_at(s))
            dt = time.monotonic() - t0
    pipeline.close()
    tok_s = args.steps * args.batch * args.seq / dt
    print(f"done: {args.steps} steps in {dt:.1f}s ({tok_s:,.0f} tok/s); "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    if reg is not None:
        import json as _json

        from ..telemetry.metrics import disable_metrics
        h = reg.histogram("train.step.seconds")
        print(f"step time: p50 {h.p50 * 1e3:.1f}ms  p99 {h.p99 * 1e3:.1f}ms  "
              f"p99.9 {h.p999 * 1e3:.1f}ms")
        # any NoC engine profiled in-process publishes noc.latency.*;
        # surface it next to the step times (logical-clock ticks)
        for key, hh in reg.histograms("noc.latency.").items():
            print(f"{key}: n={hh.count} p50 {hh.p50:.0f}  p99 {hh.p99:.0f}  "
                  f"p99.9 {hh.p999:.0f} ticks")
        snap = _json.dumps(reg.snapshot(), indent=1, sort_keys=True)
        if args.metrics == "-":
            print(snap)
        else:
            with open(args.metrics, "w") as fh:
                fh.write(snap + "\n")
            print(f"metrics snapshot -> {args.metrics}")
        disable_metrics()
    return losses


if __name__ == "__main__":
    run()
