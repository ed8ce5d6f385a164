"""The scope readers against a scoped trace recorded on a TPU v5 lite.

``data/bmvm.n4096.m1024.scoped.xplane.pb.gz`` and the ``.trace.json.gz``
that the profiler wrote beside it: one chip, n=4096, M=1024, a traced window
of 65 calls of ``iterate_kernel`` with the scopes ``bmvm.pack``,
``kernels.gf2_bmvm`` and ``bmvm.unpack`` and the kernel named ``gf2_bmvm``.
The expected numbers were read by hand from the JSON document (the ``tf_op``
and ``dur`` of its ``XLA Ops`` events).  The unscoped trace of
``test_trace.py`` stands for a program without scopes or kernel names.
"""
import types
from pathlib import Path

import pytest

from bench import run
from bench.metrics import _scopes
from bench.trace import Trace

DATA = Path(__file__).parent / "data"
PEAKS = run.load_peaks("TPU v5 lite")


@pytest.fixture(autouse=True)
def data_root(monkeypatch):
    monkeypatch.setattr(_scopes, "TRACE_ROOT", DATA)


@pytest.fixture(scope="module")
def scoped():
    return Trace.load(DATA / "bmvm.n4096.m1024.scoped.xplane.pb.gz")


@pytest.fixture(scope="module")
def unscoped():
    return Trace.load(DATA / "bmvm.n4096.m1024.xplane.pb.gz")


def ctx(tr, calls):
    return types.SimpleNamespace(trace=tr, calls=calls, window_s=tr.window_s, compiles=0,
                                 work={"min_bytes": 267_751_553.69, "step_min_s": None},
                                 peaks=PEAKS)


def read(metric, c):
    return run.load_reader(metric)(c)


def test_innermost():
    assert _scopes.innermost("jit(<unknown>)/bmvm.pack/shift_left:") == "bmvm.pack"
    assert _scopes.innermost("jit(run)/shard_map/routing.all_to_all/routing.noc_x.r1/"
                             "ppermute") == "routing.noc_x.r1"
    assert _scopes.innermost("jit(<unknown>)/while/body/closed_call/kernels.gf2_bmvm/"
                             "jit(gf2_bmvm_pallas)/gf2_bmvm/pallas_call:") == "kernels.gf2_bmvm"
    assert _scopes.innermost("v_bits:") is None and _scopes.innermost("") is None


def test_profile_found_by_window(scoped, unscoped):
    assert _scopes.find_profile(scoped) == DATA / "bmvm.n4096.m1024.scoped.trace.json.gz"
    # no JSON document beside it: no scopes to read
    assert _scopes.find_profile(unscoped) is None and _scopes.scope_map(unscoped) is None


def test_scope_map(scoped):
    names = _scopes.scope_map(scoped)
    assert list(names) == [0]
    by_scope = {}
    for scope in names[0].values():
        by_scope[scope] = by_scope.get(scope, 0) + 1
    # the copy of the argument ``v_bits`` is the one op without a scope
    assert by_scope == {"bmvm.pack": 4, "kernels.gf2_bmvm": 3, "bmvm.unpack": 2, None: 1}
    (kernel,) = [n for n, s in names[0].items() if n.startswith("%gf2_bmvm")]
    assert names[0][kernel] == "kernels.gf2_bmvm"


def test_gf2_bmvm_call_ms(scoped, unscoped):
    # 2.95196 s of the named kernel over 65 calls
    assert read("gf2_bmvm.call_ms", ctx(scoped, 65)) == pytest.approx(45.4148, abs=1e-3)
    # ``%gf2_bmvm_pallas.2``, the unnamed kernel, is not read
    assert read("gf2_bmvm.call_ms", ctx(unscoped, 63)) is None


def test_glue_ms(scoped, unscoped):
    # pack 1884.06 us and unpack 936.74 us over 65 calls
    assert read("bmvm.glue_ms", ctx(scoped, 65)) == pytest.approx(0.04339, abs=2e-5)
    assert read("bmvm.glue_ms", ctx(unscoped, 63)) is None


def test_kernel_shares_read_the_named_kernel(scoped):
    assert read("gf2_bmvm.busy_share", ctx(scoped, 65)) == pytest.approx(99.875, abs=0.01)
    assert read("gf2_bmvm.hbm_roofline", ctx(scoped, 65)) == pytest.approx(0.7199, abs=1e-3)
