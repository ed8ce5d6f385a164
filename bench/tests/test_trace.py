"""The trace reducers against a trace recorded on a TPU v5 lite.

``data/bmvm.n4096.m1024.xplane.pb.gz``: one chip, n=4096, M=1024, a traced
window of 63 calls.  The expected numbers were read by hand with
``bench/dump_trace.py``.
"""
import types
from pathlib import Path

import pytest

from bench import run
from bench.trace import Trace, length, op_name, opcode, union

DATA = Path(__file__).parent / "data"
PEAKS = run.load_peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def one():
    return Trace.load(DATA / "bmvm.n4096.m1024.xplane.pb.gz")


def ctx(tr, calls, step_min_s=None, min_bytes=None):
    return types.SimpleNamespace(trace=tr, calls=calls, window_s=tr.window_s, compiles=0,
                                 work={"step_min_s": step_min_s, "min_bytes": min_bytes},
                                 peaks=PEAKS)


def read(metric, c):
    return run.load_reader(metric)(c)


def test_intervals():
    assert union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert length([(0, 2.5), (3, 4)]) == 3.5


def test_op_names():
    name = ('%gf2_bmvm_pallas.2 = u32[1024,512]{1,0:T(8,128)S(1)} custom-call('
            's32[1024,512]{1,0:T(8,128)S(1)} %convert_element_type.3), '
            'custom_call_target="tpu_custom_call"')
    assert opcode(name) == "custom-call"
    assert op_name(name) == "%gf2_bmvm_pallas.2 custom-call u32[1024,512]"


def test_window_and_busy_one_chip(one):
    assert list(one.chips) == [0]
    assert one.window_s == pytest.approx(3.0240, abs=1e-3)
    # the kernel runs 45.4 ms of each 48.0 ms call
    assert one.busy_s() == pytest.approx(2.8642, abs=1e-3)
    assert read("device.idle", ctx(one, 63)) == pytest.approx(5.28, abs=0.01)


def test_gf2_bmvm_metrics(one):
    from bench.metrics._kernels import is_gf2_bmvm

    kernel = one.ops(0, is_gf2_bmvm)
    assert len(kernel) == 63
    assert sum(e.dur for e in kernel) == pytest.approx(2.8607, abs=1e-3)
    assert read("gf2_bmvm.busy_share", ctx(one, 63)) == pytest.approx(99.87, abs=0.01)
    # 267.75 MB at 819 GB/s = 0.3269 ms, against 45.41 ms a call
    roof = read("gf2_bmvm.hbm_roofline", ctx(one, 63, min_bytes=267_751_553.69))
    assert roof == pytest.approx(100 * 0.32692 / 45.407, rel=1e-3)


def test_step_mfu(one):
    # 63 calls of at least 0.32692 ms in a window of 3.024 s
    got = read("step_mfu", ctx(one, 63, step_min_s=3.2692e-4))
    assert got == pytest.approx(100 * 63 * 3.2692e-4 / 3.0240, rel=1e-3)
    assert read("step_mfu", ctx(one, 63)) is None


def test_idle_gaps_add_up(one):
    idle = one.window_s - length(one.busy(0))
    assert sum(s for _, s in one.gaps_by_host(0)) == pytest.approx(idle, abs=1e-6)


def test_breakdown_names(one):
    top = one.op_seconds()[0]
    assert top[0] == "%gf2_bmvm_pallas.2 custom-call u32[1024,512]"
    assert top[1] == pytest.approx(2.8607, abs=1e-3)
