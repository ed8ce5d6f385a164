"""The peaks table: keyed by ``device_kind``, no default."""
import json

import pytest

from bench import run


def test_v5e_row():
    p = run.load_peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["ici_bytes_per_s"] == 1600e9 / 8
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(run.NoChip, match="TPU v9"):
        run.load_peaks("TPU v9")


def test_every_row_has_its_source():
    table = json.loads((run.BENCH / "peaks.json").read_text())
    assert table and all(row["source"] for row in table.values())


def test_no_tpu_exits_nonzero(capsys):
    rc = run.main(["--workload", "bmvm.n24576.m128", "--seed", "1", "--seconds", "1"],
                  cache_dir=None)
    cap = capsys.readouterr()
    assert rc != 0
    assert cap.out == ""
    assert "no TPU" in cap.err


def test_unknown_device_kind_exits_nonzero(capsys, monkeypatch, tiny):
    # the look for a TPU passes; the chip's kind has no row in the table
    monkeypatch.setattr(run, "chips_for", lambda cell, allow_cpu: run.jax.devices()[:1])
    rc = run.main(["--workload", "bmvm.n24576.m128", "--seed", "1", "--seconds", "1"],
                  cache_dir=None, root=tiny)
    cap = capsys.readouterr()
    assert rc != 0
    assert cap.out == ""
    assert "no row in peaks.json" in cap.err
