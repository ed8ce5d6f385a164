"""The ``lm`` family on the CPU at a tiny size: whole runs of ``bench/run.py``
on the Jamba cell, the reference copy against the program's own, the
control and two planted faults.

The tiny copy keeps the cell's structure (one 8-layer period, a router of
width 8 holding 4 experts, the hybrid cache) at hidden size 64.  On the
chip, at the cell's own size:

    python bench/tests/test_lm.py --runs program:11:51 e4m3:21:10 token:31:0.15 expert:41:0.15

makes each ``name:seed:seconds`` run in turn, in one process: ``program``
alone or with one of ``bench.apps.lm.CONTROLS`` in its place (the e4m3
control; ``token`` and ``expert``, faults planted in the window's first
call, which a window of a few calls keeps), and prints each run's compared
count beside its limit.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[2]
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # as bench/run.py does

from bench import run  # noqa: E402
from bench.apps.lm import CONTROLS  # noqa: E402

CELL = "jamba2-mini.decode.b16.p4096"
TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 96, "vocab_size": 256, "router_width": 8,
        "num_experts": 4, "first_expert_held": 2, "mamba_dt_rank": 4}
TINY_TRAFFIC = {"batch": 4, "prompt": 24, "prefill_chunk": 8, "mamba_chunk": 4,
                "max_len": 4096, "checked": 3}


def tiny_lm_root(dest: Path) -> Path:
    """A copy of ``BENCHMARK.json`` and the files the LM cell names, its
    configuration cut to hidden size 64 and its traffic to 4 prompts of 24."""
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    (w,) = [w for w in spec["workloads"] if w["name"] == CELL]
    (c,) = [c for c in spec["configs"] if c["name"] == w["config"]]
    conf = json.loads((run.ROOT / c["file"]).read_text()) | TINY
    traffic = json.loads((run.ROOT / "bench" / "traffic" / f"{w['traffic']}.json")
                         .read_text()) | TINY_TRAFFIC
    for path, doc in ((c["file"], conf), (f"bench/traffic/{w['traffic']}.json", traffic),
                      ("BENCHMARK.json", spec)):
        (dest / path).parent.mkdir(parents=True, exist_ok=True)
        (dest / path).write_text(json.dumps(doc))
    return dest


def readings(cell, runs, **kw) -> list[dict]:
    """One result line per (name, seed, seconds) of ``runs``."""
    out = []
    for name, seed, seconds in runs:
        res = run.run_cell(cell, seed, seconds, False, t_start=time.monotonic(),
                           replace_step=CONTROLS.get(name), **kw)
        out.append(dict(res["checks"]["wrong_bits"], run=name, seed=seed,
                        correct=res["correct"], attempted=res["attempted"]))
        print(json.dumps(out[-1]), flush=True)
        gc.collect()    # a control's step holds its app in a cycle
    return out


def test_lm_cell(tmp_path, capsys):
    from bench.tests.conftest import run_tiny
    from bench.tests.test_rehearsal import check_line

    root = tiny_lm_root(tmp_path)
    cell = run.load_cell(CELL, root)
    assert [m["name"] for m in cell.end_to_end] == ["throughput", "setup_s"]
    check_line(run_tiny(root, CELL, capsys), cell, False, 1)
    res = run_tiny(root, CELL, capsys, trace=1)
    check_line(res, cell, True, 1)
    assert res["metrics"]["host.compiles"]["value"] == 0


def test_reference_copy_is_the_programs(tmp_path):
    """``bench/reference/lm.py`` (blocked, teacher-forced routing) gives the
    logits of ``repro.models.reference`` on the same float32 weights, tokens
    and routing, to float32 rounding in another order of summation."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.apps import lm
    from bench.reference import lm as copy
    from repro.models import reference
    from repro.models import transformer as T

    root = tiny_lm_root(tmp_path)
    cell = run.load_cell(CELL, root)
    cfg = lm.model_config(cell.config, cell.traffic).replace(dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          lm.make_params(cfg, jax.random.key(3)))
    tokens = lm.make_prompts(jax.random.key(4), (2, 40), cfg.vocab)
    with jax.default_matmul_precision("highest"):
        want, _, _, st = T.forward(params, {"tokens": tokens}, cfg)
    rows = jnp.arange(0, 40, 3)
    for b in range(2):
        got, slack = copy.logits_at(params, tokens[b], st["moe_experts"][:, b], rows,
                                    cell.config)
        ref = reference.forward(params, tokens[b:b + 1], cfg, st["moe_experts"][:, b:b + 1])
        scale = float(jnp.max(jnp.abs(ref)))
        assert float(jnp.max(jnp.abs(got - ref[0, rows]))) < 1e-4 * scale
        assert float(jnp.max(jnp.abs(want[b, rows] - ref[0, rows]))) < 1e-4 * scale
        # the program's own f32 routing is the reference's top-2
        assert float(np.max(slack)) <= 0


def test_layer_scopes_of_device_ops():
    """Ops map to their innermost LM scope; the grouped matmuls, which the
    TPU compiler adds without metadata, map to ``moe.experts`` by name."""
    from bench.metrics._layers import scope_of

    ragged = ('%ragged-dot-none.2 = bf16[32,14336]{1,0:T(8,128)(2,1)} custom-call(%a, %b, '
              '%c), custom_call_target="ragged_dot"')
    meta = ('%ragged-dot-metadata = (s32[9]{0}, s32[8]{0}) custom-call(%gs), '
            'custom_call_target="tpu_custom_call"')
    fusion = "%fusion.3 = bf16[16,4096]{1,0} fusion(%p), kind=kOutput"
    assert scope_of(ragged, "") == scope_of(meta, "") == "moe.experts"
    assert scope_of(fusion, "jit(decode)/while/body/mamba.mixer/dot_general") == "mamba.mixer"
    assert scope_of(fusion, "jit(decode)/moe.router/moe.combine/mul") == "moe.combine"
    assert scope_of(fusion, "jit(decode)/add") is None


def test_lm_control_is_not_correct(tmp_path):
    """The program passes; the e4m3 control and each planted fault do not."""
    cell = run.load_cell(CELL, tiny_lm_root(tmp_path))
    r = readings(cell, [("program", 2 ** 33 + 7, 0.3), ("e4m3", 2 ** 31 + 8, 0.3),
                        ("token", 2 ** 32 + 9, 0.01), ("expert", 2 ** 32 + 10, 0.01)],
                 allow_cpu=True, cache_dir=None)
    assert r[0]["correct"] and r[0]["value"] == 0
    assert not any(v["correct"] for v in r[1:])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--runs", nargs="+", required=True, metavar="NAME:SEED:SECONDS")
    args = ap.parse_args(argv)
    runs = [(n, int(s), float(t)) for n, s, t in (r.split(":") for r in args.runs)]
    if unknown := {n for n, _, _ in runs} - {"program", *CONTROLS}:
        ap.error(f"no such run {sorted(unknown)}")
    readings(run.load_cell(CELL), runs)
    return 0


if __name__ == "__main__":
    sys.exit(main())
