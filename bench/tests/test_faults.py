"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted in the program (``repro``) before set-up; the rest of
the run, the comparison with the plain reference included, is the
harness's own."""
import pytest

import repro.kernels.ops as kops
from bench.tests.conftest import ONE, run_tiny

real_gf2_bmvm = kops.gf2_bmvm


def kernel_unchanged(lut, vw, **kw):
    return vw                                   # C == R: the state comes back as it was


def kernel_half_batch(lut, vw, **kw):
    out = real_gf2_bmvm(lut, vw, **kw)
    half = vw.shape[0] // 2
    return out.at[half:].set(vw[half:])         # the second half of the vectors left out


def kernel_altered(lut, vw, **kw):
    return real_gf2_bmvm(lut, vw, **kw).at[0, 0].add(1)   # one bit flipped where made


FAULTS = [kernel_unchanged, kernel_half_batch, kernel_altered]


@pytest.mark.parametrize("fault", FAULTS, ids=[f.__name__ for f in FAULTS])
def test_fault_is_not_correct(tiny, capsys, monkeypatch, fault):
    monkeypatch.setattr(kops, "gf2_bmvm", fault)
    res = run_tiny(tiny, ONE, capsys, seconds=0.5)
    assert res["correct"] is False
    assert res["failed"] >= 1
    assert res["checks"]["wrong_bits"]["value"] > res["checks"]["wrong_bits"]["limit"]
