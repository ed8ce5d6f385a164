"""Whole runs of ``bench/run.py`` on the CPU at n=64, and finding a new cell
by name."""
import json
import shutil

from bench import run
from bench.tests.conftest import ONE, ROOT, run_tiny

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_cells_of_benchmark_json():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.chips == w["chips"] == cell.config["chips"]
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and "throughput" in names
        assert cell.per_layer and all(m["moves"] in names for m in cell.per_layer)
        for m in cell.per_layer:
            assert (run.BENCH / "metrics" / f"{m['name']}.py").exists()


def check_line(res: dict, cell: run.Cell, traced: bool, chips: int):
    assert list(res)[-1] == "checks" and [k for k in KEYS if k in res] == KEYS
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["wrong_bits"] == {"value": 0, "limit": 0}
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == chips
    want = cell.per_layer if traced else cell.end_to_end
    assert list(res["metrics"]) == [m["name"] for m in want]
    for m in want:
        got = res["metrics"][m["name"]]
        assert got["unit"] == m["unit"]
        # a CPU rehearsal prints no device or clock metric, only the counts
        assert (got["value"] is None) == (m["source"] != "program_counter")


def test_one_chip_cell(tiny, capsys):
    cell = run.load_cell(ONE, tiny)
    check_line(run_tiny(tiny, ONE, capsys), cell, False, 1)
    res = run_tiny(tiny, ONE, capsys, trace=1)
    check_line(res, cell, True, 1)
    assert res["metrics"]["host.compiles"]["value"] == 0


def test_new_cell_is_found_by_name(tiny, tmp_path, capsys):
    """A cell added as data: a traffic file and an entry in BENCHMARK.json."""
    root = tmp_path / "root"
    shutil.copytree(tiny, root)
    (root / "bench" / "traffic" / "closed.m8.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1, "batch": 8, "r": 2, "item": "vector products",
         "checked": 3}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "bmvm.n64.m8.r2", "config": "bmvm-n24576-k8",
                              "traffic": "closed.m8", "chips": 1, "why": "a test cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = run.load_cell("bmvm.n64.m8.r2", root)
    assert cell.traffic["batch"] == 8 and cell.traffic["r"] == 2
    res = run_tiny(root, "bmvm.n64.m8.r2", capsys)
    check_line(res, cell, False, 1)
