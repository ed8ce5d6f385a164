"""CPU tests of the chip benchmark: ``JAX_PLATFORMS=cpu python -m pytest bench/tests``.

Runs go through ``bench.run.main`` with ``allow_cpu=True``, which skips
only the look for a TPU, and with a tiny copy of each cell (n=64) under a
temporary root.
"""
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

ONE = "bmvm.n24576.m128"


def tiny_root(dest: Path, n: int = 64, batch: int = 16) -> Path:
    """A copy of ``BENCHMARK.json`` and the files it names, every
    configuration cut to ``n`` and every traffic mix to ``batch`` vectors."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["n"] = n
        (dest / c["file"]).parent.mkdir(parents=True, exist_ok=True)
        (dest / c["file"]).write_text(json.dumps(cfg))
    (dest / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    for w in spec["workloads"]:
        t = json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        t["batch"] = batch
        (dest / "bench" / "traffic" / f"{w['traffic']}.json").write_text(json.dumps(t))
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    return dest


def run_tiny(root: Path, workload: str, capsys, seed: int = 2 ** 31 + 5,
             seconds: float = 0.3, trace: int = 0) -> dict:
    """One rehearsal run of ``workload`` under ``root``; its last stdout line."""
    from bench import run

    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                   "--trace", str(trace)], allow_cpu=True, cache_dir=None,
                  trace_root=root / "traces", root=root)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


@pytest.fixture(scope="module")
def tiny(tmp_path_factory) -> Path:
    return tiny_root(tmp_path_factory.mktemp("tiny"))
