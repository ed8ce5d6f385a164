"""The control: the plain reference one precision step down, put in the
program's place, comes out not correct.

The configurations state exact GF(2) products.  The control accumulates
the counts of ones in bfloat16 (``bench.apps.bmvm.control_product``), the
step below the int32 or float32 counts that are exact; its parities are
wrong wherever a count passes 256.  As a test it runs on the CPU at n=1024,
where the counts lie near 256.  On the chip, at a cell's own size:

    python bench/tests/test_control.py --workload bmvm.n24576.m128 \\
        --seeds 11 12 13 --control-seeds 21 22 23 --seconds 3

runs the program on ``--seeds`` and the control on ``--control-seeds``, all
in one process, and prints each run's compared number beside its limit.
"""
from __future__ import annotations

import argparse
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
from bench.apps.bmvm import control_product  # noqa: E402


def control(app):
    """The control in the program's place."""
    return lambda v: control_product(app.a, v)


def readings(cell, seeds, control_seeds, seconds, **kw) -> dict:
    out = {"program": {}, "control": {}}
    for key, ss, step in (("program", seeds, None), ("control", control_seeds, control)):
        for s in ss:
            res = run.run_cell(cell, s, seconds, False, t_start=time.monotonic(),
                               replace_step=step, **kw)
            out[key][s] = dict(res["checks"]["wrong_bits"], correct=res["correct"],
                               attempted=res["attempted"])
            print(json.dumps({key: s, **out[key][s]}), flush=True)
    return out


def test_control_is_not_correct(tmp_path):
    from bench.tests.conftest import ONE, tiny_root

    root = tiny_root(tmp_path, n=1024, batch=32)
    cell = run.load_cell(ONE, root)
    r = readings(cell, [7], [2 ** 31 + 8, 9], 0.2, allow_cpu=True, cache_dir=None)
    assert all(v["correct"] and v["value"] == 0 for v in r["program"].values())
    for v in r["control"].values():
        assert not v["correct"]
        # counts ~ Binomial(1024, 1/4): most pass 256 and lose their last bit
        assert v["value"] > 0.01 * 32 * 1024


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    r = readings(run.load_cell(args.workload), args.seeds, args.control_seeds, args.seconds)
    lower = max((v["value"] for v in r["program"].values()), default=None)
    upper = min(v["value"] for v in r["control"].values())
    print(json.dumps({"workload": args.workload, "lower": lower, "upper": upper,
                      "control_correct": any(v["correct"] for v in r["control"].values())}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
