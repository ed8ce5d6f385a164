"""Serving driver: batched prefill + decode with a continuous request queue.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \\
        --requests 16 --batch 4 --prompt-len 32 --gen 16

Implements the batched serving loop the decode shapes lower: requests are
grouped into fixed-size batches, each batch is prefilled once, then decoded
token-by-token with a shared ring cache (greedy sampling).

``--metrics PATH`` turns on the telemetry metrics registry: prefill and
per-token decode wall-clock land in the ``serve.prefill.seconds`` /
``serve.decode.seconds`` histograms; the JSON snapshot (with p50/p99/p99.9)
is written to PATH ('-' = stdout).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..configs import get_config
from ..models import transformer as T
from ..models.layers import init_params
from .cache import use_compile_cache
from .mesh import make_host_mesh


def serve_batch(params, cfg, prompts: np.ndarray, gen: int, mesh,
                reg=None) -> np.ndarray:
    """One batch: prefill once, decode token-by-token.  ``reg``: an optional
    telemetry MetricsRegistry — per-phase wall clock is observed into the
    ``serve.prefill.seconds`` / ``serve.decode.seconds`` histograms (each
    sample is synced via the host round-trip, so it bounds real latency)."""
    B, S = prompts.shape
    with jax.set_mesh(mesh):
        cache = T.init_cache(cfg, B, S + gen)
        batch = {"tokens": jnp.asarray(prompts)}
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros((B, cfg.enc_seq, cfg.d_frontend), cfg.cdtype)
        if cfg.family == "vlm":
            batch["patches"] = jnp.zeros((B, cfg.n_patches, cfg.d_frontend), cfg.cdtype)
        prefill = jax.jit(lambda p, b, c: T.prefill(p, b, cfg, c))
        decode = jax.jit(lambda p, b, c: T.decode_step(p, b, cfg, c))
        ts = time.perf_counter()
        logits, cache = prefill(params, batch, cache)
        tok = jnp.argmax(logits[:, -1], -1)
        out = [np.asarray(tok)]
        if reg is not None:
            reg.histogram("serve.prefill.seconds").observe(
                time.perf_counter() - ts)
        for _ in range(gen - 1):
            ts = time.perf_counter()
            logits, cache = decode(params, {"tokens": tok[:, None]}, cache)
            tok = jnp.argmax(logits, -1)
            out.append(np.asarray(tok))
            if reg is not None:
                reg.histogram("serve.decode.seconds").observe(
                    time.perf_counter() - ts)
    return np.stack(out, 1)


def run(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--model-parallel", type=int, default=1)
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
    params = init_params(T.abstract_params(cfg), jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)

    t0 = time.monotonic()
    done = 0
    all_out = []
    while done < args.requests:
        n = min(args.batch, args.requests - done)
        prompts = rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)).astype(np.int32)
        out = serve_batch(params, cfg, prompts, args.gen, mesh, reg=reg)
        all_out.append(out[:n])
        done += n
        print(f"served {done}/{args.requests} requests "
              f"(batch decode tok/s so far: {done * args.gen / (time.monotonic() - t0):,.1f})")
    dt = time.monotonic() - t0
    print(f"done: {args.requests} requests × {args.gen} tokens in {dt:.1f}s")
    if reg is not None:
        import json as _json

        from ..telemetry.metrics import disable_metrics
        d = reg.histogram("serve.decode.seconds")
        print(f"decode/token: p50 {d.p50 * 1e3:.1f}ms  "
              f"p99 {d.p99 * 1e3:.1f}ms  p99.9 {d.p999 * 1e3:.1f}ms")
        # any NoC engine profiled in-process publishes noc.latency.*;
        # surface it next to the serve latencies (logical-clock ticks)
        for key, h in reg.histograms("noc.latency.").items():
            print(f"{key}: n={h.count} p50 {h.p50:.0f}  p99 {h.p99:.0f}  "
                  f"p99.9 {h.p999:.0f} ticks")
        snap = _json.dumps(reg.snapshot(), indent=1, sort_keys=True)
        if args.metrics == "-":
            print(snap)
        else:
            with open(args.metrics, "w") as fh:
                fh.write(snap + "\n")
            print(f"metrics snapshot -> {args.metrics}")
        disable_metrics()
    return np.concatenate(all_out)


if __name__ == "__main__":
    run()
