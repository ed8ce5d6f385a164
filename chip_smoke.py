"""Smoke run of the system's main paths on TPU chips, in one process.

    python chip_smoke.py               # one chip: case-study kernels, NoC
                                       # executor, LM serving and training
    python chip_smoke.py --four-chips  # the multi-chip NoC step, on 4 chips

Every phase goes through the entry points a user calls, at real widths, and
checks what comes out against the repository's own references.  A failed
check raises, so the exit code is non-zero and no result line is printed.
The last line of a passing run is one JSON object naming the device.  There
is no CPU fallback: where JAX finds no TPU the script exits non-zero before
any phase runs.  The phase functions take their sizes as arguments, so they
can be rehearsed at tiny sizes on the CPU (``kernel_marker=None`` there, as
interpret mode lowers no kernel).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.launch.cache import use_compile_cache  # noqa: E402

KERNEL_MARKER = "tpu_custom_call"   # the Mosaic kernel call in compiled HLO


class SmokeFailure(RuntimeError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, text: str, t0: float) -> None:
    print(f"[{phase}] {text} -> ok ({time.monotonic() - t0:.1f} s)", flush=True)


def _compile_with_kernel(fn, args, kernel_marker):
    """jit+compile ``fn`` for ``args``; check the compiled program calls the
    kernel, so what runs is the compiled kernel and not the interpreter."""
    compiled = jax.jit(fn).lower(*args).compile()
    if kernel_marker is not None:
        check(kernel_marker in compiled.as_text(), f"no {kernel_marker} in HLO")
    return compiled


def _marker_note(kernel_marker):
    return f"{kernel_marker} in HLO" if kernel_marker else "interpret mode"


# ---------------------------------------------------------------------------
# one chip: case-study kernels through the app entry points
# ---------------------------------------------------------------------------

def phase_bmvm(n: int, k: int, m: int, r: int, seed: int,
               kernel_marker=KERNEL_MARKER, n_sw: int = 16) -> None:
    from repro.apps import bmvm

    t0 = time.monotonic()
    rng = np.random.default_rng(seed)
    cfg = bmvm.BMVMConfig(n=n, k=k)
    a = rng.integers(0, 2, (n, n)).astype(np.uint8)
    v = rng.integers(0, 2, (m, n)).astype(np.uint8)
    lut = bmvm.preprocess(a, cfg)
    vj = jnp.asarray(v)
    run = _compile_with_kernel(lambda l_, v_: bmvm.iterate_kernel(l_, v_, cfg, r),
                               (lut, vj), kernel_marker)
    out = np.asarray(run(lut, vj))
    ref = np.asarray(jax.jit(lambda l_, v_: bmvm.iterate_kernel(
        l_, v_, cfg, r, use_kernel=False))(lut, vj))
    check(np.array_equal(out, ref), "bmvm: kernel != use_kernel=False")
    sw = bmvm.software_ref(a, v[:n_sw], r)
    check(np.array_equal(out[:n_sw], sw), "bmvm: kernel != software_ref")
    report("bmvm", f"iterate_kernel n={n} k={k} lut={tuple(lut.shape)} "
           f"{lut.dtype} ({lut.nbytes / 2**20:.0f} MiB) M={m} r={r}: "
           f"{_marker_note(kernel_marker)}; bit-exact vs use_kernel=False "
           f"(all {m}) and software_ref (first {n_sw})", t0)


def phase_ldpc(copies: int, batch: int, iters: int, snr_db: float, seed: int,
               kernel_marker=KERNEL_MARKER) -> None:
    from repro.apps import ldpc

    t0 = time.monotonic()
    rng = np.random.default_rng(seed)
    H = ldpc.pg_ldpc_H(copies=copies)
    idx = ldpc.build_edge_index(H)
    n_bits = H.shape[1]
    # all-zero codeword: the code is linear and the channel symmetric
    llr = jnp.asarray(np.stack([ldpc.awgn_llr(np.zeros(n_bits, np.int8), snr_db, rng)
                                for _ in range(batch)]))
    run = _compile_with_kernel(lambda x: ldpc.decode_minsum(idx, x, iters),
                               (llr,), kernel_marker)
    bits, post = (np.asarray(x) for x in run(llr))
    bits_r, post_r = (np.asarray(x) for x in jax.jit(
        lambda x: ldpc.decode_minsum(idx, x, iters, use_kernel=False))(llr))
    # min-sum only compares and flips signs: no rounding, so no tolerance
    check(np.array_equal(bits, bits_r), "ldpc: decoded bits differ")
    check(np.array_equal(post, post_r), "ldpc: posteriors differ")
    raw_ber = float((np.asarray(llr) < 0).mean())
    ber = float(bits.mean())
    check(ber < raw_ber, f"ldpc: decoding raised the bit error rate {raw_ber} -> {ber}")
    report("ldpc", f"decode_minsum pg_ldpc_H(copies={copies}) N={n_bits} "
           f"dc={idx.check_edges.shape[1]} batch={batch} iters={iters} "
           f"snr={snr_db} dB: {_marker_note(kernel_marker)}; bits and "
           f"posteriors bit-exact vs use_kernel=False; BER {raw_ber:.5f} -> "
           f"{ber:.5f}", t0)


# f32 tolerance of the particle filter's kernel against its oracle.  Per call
# the two sum the same 1024 products in another order: 1e-5 on normalized
# histograms and Bhattacharyya coefficients.  Over a track, ROI windows are
# placed by flooring the particle centres, so a rounding difference can move
# one particle's window by a pixel; 1e-2 px on the centres bounds that for a
# particle carrying up to 1% of the weight.
PF_CALL_ATOL = 1e-5
PF_TRACK_ATOL_PX = 1e-2


def phase_pf(img: int, roi: int, n_particles: int, n_bins: int, frames_n: int,
             seed: int, kernel_marker=KERNEL_MARKER) -> None:
    from repro.apps import particle_filter as pf
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    t0 = time.monotonic()
    rng = np.random.default_rng(seed)
    cfg = pf.PFConfig(img=img, roi=roi, n_particles=n_particles, n_bins=n_bins,
                      seed=seed)
    frames, truth = pf.synth_video(cfg, frames_n, rng)
    # the oracle's einsum at full f32: TPU's default matmul precision would
    # round its operands to bf16 and set the oracle, not the kernel, apart
    with jax.default_matmul_precision("highest"):
        f0, f1 = jnp.asarray(frames[0]), jnp.asarray(frames[1])
        c0 = jnp.asarray(truth[0], jnp.float32)
        ref_hist = pf.reference_histogram(f0, c0, cfg)
        _compile_with_kernel(
            lambda f, c, h, key: pf.step(f, c, h, cfg, key)[0],
            (f1, c0, ref_hist, jax.random.key(seed)), kernel_marker)
        parts = c0[None] + jax.random.normal(jax.random.key(seed), (n_particles, 2)) * 3.0
        bins = pf._roi_bins(f1, parts, cfg)
        dw = pf.distance_weights(cfg)
        hist, bc = kops.particle_histogram(bins, dw, ref_hist, n_bins=n_bins)
        hist_r = kref.weighted_histogram(bins, dw, n_bins)
        bc_r = kref.bhattacharyya(hist_r, ref_hist)
        call_err = max(float(jnp.max(jnp.abs(hist - hist_r))),
                       float(jnp.max(jnp.abs(bc - bc_r))))
        check(call_err <= PF_CALL_ATOL, f"pf: kernel call off by {call_err}")
        est = pf.track(frames, cfg)
        est_r = pf.track(frames, cfg, use_kernel=False)
    track_err = float(np.abs(est - est_r).max())
    check(track_err <= PF_TRACK_ATOL_PX, f"pf: tracks differ by {track_err} px")
    px_err = float(np.linalg.norm(est - truth, axis=1).mean())
    check(px_err < roi / 2, f"pf: lost the target, mean error {px_err} px")
    report("pf", f"track PFConfig(img={img}, roi={roi}, n_particles={n_particles}, "
           f"n_bins={n_bins}) frames={frames_n}: {_marker_note(kernel_marker)}; "
           f"one call vs oracle max err {call_err:.3g} (tol {PF_CALL_ATOL}); "
           f"track vs use_kernel=False max {track_err:.3g} px "
           f"(tol {PF_TRACK_ATOL_PX}); mean pixel error vs truth {px_err:.3f}", t0)


def phase_noc(seed: int) -> None:
    """The NoC executor's jitted PE firing against the direct oracle."""
    from repro.apps import bmvm, ldpc

    t0 = time.monotonic()
    rng = np.random.default_rng(seed)
    H = ldpc.fano_plane_H()
    llr = ldpc.awgn_llr(np.zeros(7, np.int8), 3.0, rng)
    b_sim, p_sim, st = ldpc.decode_on_noc(H, llr, 8, mode="sim")
    b_dir, p_dir, _ = ldpc.decode_on_noc(H, llr, 8, mode="direct")
    check(np.array_equal(b_sim, b_dir) and np.array_equal(p_sim, p_dir),
          "noc: ldpc sim != direct")
    cfg = bmvm.BMVMConfig()
    a = rng.integers(0, 2, (cfg.n, cfg.n)).astype(np.uint8)
    v = rng.integers(0, 2, (1, cfg.n)).astype(np.uint8)
    lut = bmvm.preprocess(a, cfg)
    o_sim, bst = bmvm.iterate_noc_sim(lut, v, cfg, 3, mode="sim")
    o_dir, _ = bmvm.iterate_noc_sim(lut, v, cfg, 3, mode="direct")
    check(np.array_equal(o_sim, o_dir), "noc: bmvm sim != direct")
    check(np.array_equal(o_sim.reshape(1, -1), bmvm.software_ref(a, v, 3)),
          "noc: bmvm != software_ref")
    report("noc", f"NoCExecutor mode=sim vs direct: ldpc Fano code on mesh16 "
           f"8 iters ({st.rounds} rounds, {st.flits} flits), bmvm n={cfg.n} "
           f"on {cfg.topology} r=3 ({bst.rounds} rounds); bit-identical", t0)


# ---------------------------------------------------------------------------
# one chip: LM serving at published widths, and a smoke training run
# ---------------------------------------------------------------------------

# Cache consistency: prefill + decoding through the cache against the
# uncached forward over the same tokens, at the generated positions.
#
# The served model: f32 parameters, bf16 compute.  Each layer rounds its
# activations to bf16 (8 significant bits); where the two paths' matmuls
# accumulate in another order, their roundings part by an ulp, so the logits
# must agree within 2^-4 of the largest one.  A broken cache (wrong
# positions, stale or missing keys) moves logits by their own size.
#
# That tolerance is fragile at full depth: this repository's random init
# gives the attention projections std 1/sqrt(n_heads) rather than
# 1/sqrt(d_model), attention is near-argmax, and a one-ulp difference grows
# about 3.5x per layer (on the CPU, d_model=256, f32: 2e-5 after one layer,
# 1.0 on logits of 1.6 after 16).  So the cache is also checked where the
# check is well conditioned: the published widths with the depth cut to 2
# and f32 compute at full matmul precision, where the two paths part by f32
# rounding (2e-4 of the largest logit on the CPU) and the tolerance is 1e-2.
LM_BF16_RTOL = 2 ** -4
LM_CHECK_LAYERS = 2
LM_F32_RTOL = 1e-2


def _cache_vs_forward(cfg, params, prompts: np.ndarray, toks: np.ndarray):
    """Logits (B, gen, V) at the generated positions: teacher-forced through
    prefill + decode with a cache, and from one uncached forward."""
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as T

    (batch, prompt), gen = prompts.shape, toks.shape[1]
    with jax.set_mesh(make_host_mesh()):
        prefill = jax.jit(lambda p, b, c: T.prefill(p, b, cfg, c))
        decode = jax.jit(lambda p, b, c: T.decode_step(p, b, cfg, c))
        logits, cache = prefill(params, {"tokens": jnp.asarray(prompts)},
                                T.init_cache(cfg, batch, prompt + gen))
        cached = [logits[:, -1]]
        for t in range(gen - 1):
            logits, cache = decode(params, {"tokens": jnp.asarray(toks[:, t:t + 1])},
                                   cache)
            cached.append(logits)
        seq = jnp.asarray(np.concatenate([prompts, toks[:, :-1]], 1))
        full = jax.jit(lambda p, b: T.forward(p, b, cfg)[0])(params, {"tokens": seq})
        return (np.asarray(jnp.stack(cached, 1), np.float32),
                np.asarray(full[:, prompt - 1:], np.float32))


def phase_serve(arch: str, smoke: bool, requests: int, batch: int, prompt: int,
                gen: int, seed: int) -> None:
    from repro.configs import get_config
    from repro.launch import serve
    from repro.models import transformer as T
    from repro.models.layers import init_params

    t0 = time.monotonic()
    argv = ["--arch", arch, "--requests", str(requests), "--batch", str(batch),
            "--prompt-len", str(prompt), "--gen", str(gen), "--seed", str(seed)]
    served = serve.run(argv + (["--smoke"] if smoke else []))
    check(served.shape == (requests, gen), f"serve: output shape {served.shape}")
    cfg = get_config(arch, smoke=smoke)
    check(((served >= 0) & (served < cfg.vocab)).all(), "serve: token out of vocab")
    t_serve = time.monotonic() - t0

    # replay the first batch: the same seed gives serve.run's params and prompts
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab, (batch, prompt)).astype(np.int32)
    toks = served[:batch]
    params = init_params(T.abstract_params(cfg), jax.random.key(seed))
    cached, uncached = _cache_vs_forward(cfg, params, prompts, toks)
    del params
    check(np.array_equal(cached.argmax(-1), toks),
          "serve: replayed greedy tokens differ from the served ones")
    deep_err = float(np.abs(cached - uncached).max())
    deep_scale = float(np.abs(uncached).max())
    check(np.isfinite(cached).all() and deep_err <= LM_BF16_RTOL * deep_scale,
          f"serve: cached logits off by {deep_err} (max |logit| {deep_scale})")

    ref_cfg = cfg.replace(n_layers=LM_CHECK_LAYERS, dtype="float32")
    with jax.default_matmul_precision("highest"):
        params = init_params(T.abstract_params(ref_cfg), jax.random.key(seed))
        cached, uncached = _cache_vs_forward(ref_cfg, params, prompts, toks)
        del params
    scale = float(np.abs(uncached).max())
    err = float(np.abs(cached - uncached).max())
    check(np.isfinite(cached).all() and err <= LM_F32_RTOL * scale,
          f"serve: cached logits off by {err} (max |logit| {scale})")
    report("serve", f"launch.serve.run --arch {arch}{' --smoke' if smoke else ''} "
           f"({cfg.n_layers}L d={cfg.d_model} vocab={cfg.vocab} "
           f"{cfg.param_count() / 1e9:.2f}B params f32, {cfg.dtype} compute) "
           f"requests={requests} batch={batch} prompt={prompt} gen={gen} in "
           f"{t_serve:.1f} s; replayed greedy tokens equal; cached vs uncached "
           f"logits max err {deep_err:.4g} of max |logit| {deep_scale:.4g} "
           f"(rtol {LM_BF16_RTOL}), at {LM_CHECK_LAYERS}L f32 {err:.4g} of "
           f"{scale:.4g} (rtol {LM_F32_RTOL})", t0)


def phase_train(arch: str, steps: int, batch: int, seq: int, seed: int) -> None:
    from repro.launch import train

    t0 = time.monotonic()
    losses = train.run(["--arch", arch, "--smoke", "--steps", str(steps),
                        "--batch", str(batch), "--seq", str(seq),
                        "--seed", str(seed), "--log-every", "1"])
    check(len(losses) == steps and np.isfinite(losses).all(),
          f"train: losses {losses}")
    report("train", f"launch.train.run --arch {arch} --smoke steps={steps} "
           f"batch={batch} seq={seq}: losses {[round(x, 4) for x in losses]} "
           f"finite", t0)


# ---------------------------------------------------------------------------
# four chips: the paper's multi-FPGA step
# ---------------------------------------------------------------------------

def _same_stats(a, b) -> bool:
    return a.as_dict() == b.as_dict()


def phase_four_chips(n: int, m: int, r: int, seed: int, n_chips: int = 4) -> None:
    from repro.apps import bmvm, ldpc
    from repro.core import make_topology
    from repro.core.partition import PartitionPlan, mesh_for_partition, mesh_for_topology

    t0 = time.monotonic()
    devices = jax.devices()
    check(len(devices) >= n_chips, f"four-chips: {len(devices)} devices")
    rng = np.random.default_rng(seed)
    H = ldpc.fano_plane_H()
    llr = ldpc.awgn_llr(np.zeros(7, np.int8), 3.0, rng)
    cfg = bmvm.BMVMConfig()
    a = rng.integers(0, 2, (cfg.n, cfg.n)).astype(np.uint8)
    v = rng.integers(0, 2, (1, cfg.n)).astype(np.uint8)
    lut = bmvm.preprocess(a, cfg)
    pods = [0, 0, 1, 1]
    lines = []
    for topo in ("mesh", "fattree"):
        mesh = mesh_for_topology(make_topology(topo, n_chips))
        pmesh = mesh_for_partition(make_topology(topo, n_chips),
                                   PartitionPlan({}, tuple(pods), (), ()))
        for mm in (mesh, pmesh):
            check(len({d.id for d in mm.devices.flat}) == n_chips,
                  f"four-chips: {topo} mesh spans {mm.devices}")
        ld = {}
        for key, kw in (("sim", dict(mode="sim")), ("spmd", dict(mode="spmd")),
                        ("cut_sim", dict(mode="sim", pods=pods)),
                        ("cut_spmd", dict(mode="spmd", pods=pods))):
            ld[key] = ldpc.decode_on_noc(H, llr, 8, topology=topo, n_nodes=n_chips, **kw)
        bm = {}
        for key, kw in (("sim", dict(mode="sim")), ("spmd", dict(mode="spmd")),
                        ("cut_sim", dict(mode="sim", pods=pods)),
                        ("cut_spmd", dict(mode="spmd", pods=pods))):
            bm[key] = bmvm.iterate_noc_sim(lut, v, cfg, 3, topology=topo,
                                           n_nodes=n_chips, **kw)
        for app, res, n_out in (("ldpc", ld, 2), ("bmvm", bm, 1)):
            for x, y in (("spmd", "sim"), ("cut_spmd", "cut_sim"), ("cut_spmd", "sim")):
                check(all(np.array_equal(p, q)
                          for p, q in zip(res[x][:n_out], res[y][:n_out])),
                      f"four-chips: {app} {topo} {x} outputs != {y}")
            check(_same_stats(res["spmd"][-1], res["sim"][-1]),
                  f"four-chips: {app} {topo} spmd NoCStats != sim")
            check(_same_stats(res["cut_spmd"][-1], res["cut_sim"][-1]),
                  f"four-chips: {app} {topo} bridged spmd NoCStats != sim")
            st = res["cut_spmd"][-1]
            check(st.cross_pod_msgs > 0, f"four-chips: {app} {topo} cut carries nothing")
            lines.append(f"{app}/{topo}: rounds={res['spmd'][-1].rounds} "
                         f"cut_msgs={st.cross_pod_msgs}")
    check(np.array_equal(bm["sim"][0].reshape(1, -1), bmvm.software_ref(a, v, 3)),
          "four-chips: bmvm != software_ref")

    kcfg = bmvm.BMVMConfig(n=n, k=8)
    ka = rng.integers(0, 2, (n, n)).astype(np.uint8)
    kv = jnp.asarray(rng.integers(0, 2, (m, n)).astype(np.uint8))
    klut = bmvm.preprocess(ka, kcfg)
    out_spmd = bmvm.iterate_spmd(klut, kv, kcfg, r, topology="fattree")
    shard_devs = {s.device.id for s in out_spmd.addressable_shards}
    check(len(shard_devs) == n_chips, f"four-chips: iterate_spmd shards on {shard_devs}")
    out_k = bmvm.iterate_kernel(klut, kv, kcfg, r)
    check(np.array_equal(np.asarray(out_spmd), np.asarray(out_k)),
          "four-chips: iterate_spmd != iterate_kernel")
    report("four-chips", f"{n_chips} {devices[0].platform} devices: NoC spmd == sim "
           f"(outputs and NoCStats) for ldpc Fano and bmvm n={cfg.n} on mesh and "
           f"fattree, bridged pods={pods} == unpartitioned ({'; '.join(lines)}); "
           f"iterate_spmd n={n} M={m} r={r} shards on devices {sorted(shard_devs)} "
           f"== iterate_kernel", t0)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip NoC phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    print(f"devices: {len(devices)} x {dev.device_kind} ({dev.platform}); "
          f"jax {jax.__version__}; compile cache {cache_dir}", flush=True)
    s = args.seed
    if args.four_chips:
        phase_four_chips(n=4096, m=256, r=2, seed=s)
    else:
        phase_bmvm(n=4096, k=8, m=1024, r=4, seed=s)
        phase_ldpc(copies=186, batch=256, iters=10, snr_db=2.0, seed=s)
        phase_pf(img=256, roi=32, n_particles=1024, n_bins=16, frames_n=8, seed=s)
        phase_noc(seed=s)
        phase_serve("llama3.2-1b", smoke=False, requests=8, batch=4, prompt=128,
                    gen=16, seed=s)
        phase_train("llama3.2-1b", steps=3, batch=8, seq=128, seed=s)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
