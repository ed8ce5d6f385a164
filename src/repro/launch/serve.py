"""Serving driver: greedy batched prefill and decode through the model's cache.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \\
        --requests 16 --batch 4 --prompt-len 32 --gen 16

Requests are served in fixed-size batches, one batch after another: each
batch is prefilled once, then decoded token by token with greedy sampling
through its cache (attention keys and values beside recurrent state, as the
model's layers need).  There is no request queue and no continuous batching.

The jitted ``prefill`` and ``decode`` of a config are built once
(:func:`steps`) and serve every batch; the chip benchmark's LM cell calls
the same :func:`prefill` and :func:`decode`.  Each call runs inside a host
span, ``serve.prefill`` or ``serve.decode`` (``jax.profiler
.TraceAnnotation``), which names the host's share of a profiled window.
Parameters are served in ``cfg.serve_param_dtype``.

``--metrics PATH`` turns on the telemetry metrics registry: prefill and
per-token decode wall-clock land in the ``serve.prefill.seconds`` /
``serve.decode.seconds`` histograms; the JSON snapshot (with p50/p99/p99.9)
is written to PATH ('-' = stdout).
"""
from __future__ import annotations

import argparse
import functools
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..configs import get_config
from ..configs.base import ModelConfig
from ..models import transformer as T
from ..models.layers import init_params
from .cache import use_compile_cache
from .mesh import make_host_mesh


def new_state(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """A batch's decode state: the model's cache, every sequence's tokens
    (prompt and chosen) at their positions, and, where the model has MoE
    layers, the expert ids they routed every position to,
    (n_moe_layers, batch, max_len, top_k)."""
    state = {"cache": T.init_cache(cfg, batch, max_len),
             "tokens": jnp.zeros((batch, max_len), jnp.int32)}
    if n_moe := cfg.n_periods * sum(f == "moe" for _, f in cfg.pattern):
        state["experts"] = jnp.zeros((n_moe, batch, max_len, cfg.top_k), jnp.int32)
    return state


def _advance(state: dict, tokens: jax.Array, cache: dict, moe_stats: dict) -> dict:
    """``state`` after ``tokens`` (B, S) went through the model into ``cache``."""
    out = {"cache": cache,
           "tokens": lax.dynamic_update_slice(state["tokens"], tokens,
                                              (0, cache["pos"] - tokens.shape[1]))}
    if "experts" in state:
        ex = moe_stats["moe_experts"]
        out["experts"] = lax.dynamic_update_slice(state["experts"], ex,
                                                  (0, 0, cache["pos"] - ex.shape[2], 0))
    return out


@functools.cache
def steps(cfg: ModelConfig):
    """The jitted ``(prefill, decode)`` of ``cfg``, built once.

    ``prefill(params, batch, state)`` runs ``batch["tokens"]`` (B, S) from the
    cache's position on; ``decode(params, logits, state)`` takes the greedy
    choice of ``logits`` (B, V) as every sequence's next token.  Each returns
    the logits of the last position, (B, V), and the state; the state is
    donated."""

    def prefill(params, batch, state):
        logits, _, cache, st = T.forward(params, batch, cfg, state["cache"])
        return logits[:, -1], _advance(state, batch["tokens"], cache, st)

    def decode(params, logits, state):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        out, _, cache, st = T.forward(params, {"tokens": tok}, cfg, state["cache"])
        return out[:, -1], _advance(state, tok, cache, st)

    return jax.jit(prefill, donate_argnums=(2,)), jax.jit(decode, donate_argnums=(2,))


def prefill(params, batch: dict, state: dict, cfg: ModelConfig):
    with jax.profiler.TraceAnnotation("serve.prefill"):
        return steps(cfg)[0](params, batch, state)


def decode(params, logits: jax.Array, state: dict, cfg: ModelConfig):
    with jax.profiler.TraceAnnotation("serve.decode"):
        return steps(cfg)[1](params, logits, state)


def serve_params(cfg: ModelConfig, key: jax.Array):
    """Random parameters from ``key``, in ``cfg.serve_param_dtype``."""
    params = init_params(T.abstract_params(cfg), key)
    dtype = jnp.dtype(cfg.serve_param_dtype)
    return jax.tree.map(lambda a: a.astype(dtype), params)


def serve_batch(params, cfg, prompts: np.ndarray, gen: int, mesh,
                reg=None) -> np.ndarray:
    """One batch: prefill once, decode token-by-token; the (B, gen) tokens
    chosen.  ``reg``: an optional telemetry MetricsRegistry — per-phase wall
    clock is observed into the ``serve.prefill.seconds`` /
    ``serve.decode.seconds`` histograms (each sample waits for the step's
    logits, so it bounds real latency)."""
    B, S = prompts.shape
    extra = cfg.n_patches if cfg.family == "vlm" else 0
    with jax.set_mesh(mesh):
        state = new_state(cfg, B, extra + S + gen)
        batch = {"tokens": jnp.asarray(prompts)}
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros((B, cfg.enc_seq, cfg.d_frontend), cfg.cdtype)
        if cfg.family == "vlm":
            batch["patches"] = jnp.zeros((B, cfg.n_patches, cfg.d_frontend), cfg.cdtype)
        ts = time.perf_counter()
        logits, state = prefill(params, batch, state, cfg)
        if reg is not None:
            logits.block_until_ready()
            reg.histogram("serve.prefill.seconds").observe(time.perf_counter() - ts)
        for _ in range(gen - 1):
            ts = time.perf_counter()
            logits, state = decode(params, logits, state, cfg)
            if reg is not None:
                logits.block_until_ready()
                reg.histogram("serve.decode.seconds").observe(time.perf_counter() - ts)
        chosen = np.asarray(state["tokens"][:, extra + S:extra + S + gen - 1])
        last = np.asarray(jnp.argmax(logits, axis=-1), np.int32)[:, None]
    return np.concatenate([chosen, last], axis=1)


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
    params = serve_params(cfg, jax.random.key(args.seed))
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
