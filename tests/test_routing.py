"""Routing schedules: exactly-once delivery == device transpose, both in the
numpy simulator and (subprocess, 12 fake devices) the shard_map collectives."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import make_topology, simulate_schedule
from tests.conftest import run_with_devices


@given(st.sampled_from(["ring", "mesh", "torus", "fattree"]),
       st.sampled_from([2, 4, 6, 9, 12, 16]),
       st.integers(1, 9))
@settings(max_examples=30, deadline=None)
def test_simulator_is_transpose(name, n, c):
    """Every message delivered exactly once to the right node (the property
    CONNECT's flow control guarantees; here by schedule construction)."""
    rng = np.random.default_rng(n * 100 + c)
    topo = make_topology(name, n)
    msgs = rng.integers(0, 255, size=(n, n, c), dtype=np.uint8)
    out, stats = simulate_schedule(topo, msgs)
    assert np.array_equal(out, msgs.swapaxes(0, 1))
    assert stats.rounds <= topo.a2a_rounds()


def test_round_counts_match_model():
    for name in ("ring", "mesh", "torus", "fattree"):
        topo = make_topology(name, 16)
        msgs = np.ones((16, 16, 4), np.uint8)
        _, stats = simulate_schedule(topo, msgs)
        assert stats.rounds == topo.a2a_rounds(), name


@pytest.mark.slow
def test_shard_map_schedules_match_oracle():
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import make_topology
from repro.core.routing import all_to_all_for, topology_axes
for name in ("ring","mesh","torus","fattree"):
    for n in (4, 12):
        topo = make_topology(name, n)
        axes = topology_axes(topo)
        devs = np.array(jax.devices()[:n]).reshape([s for _, s in axes])
        mesh = Mesh(devs, [a for a, _ in axes])
        fn = all_to_all_for(topo)
        x = jnp.arange(n*n*3, dtype=jnp.float32).reshape(n, n, 3)
        in_spec = P(tuple(a for a,_ in axes)) if len(axes)>1 else P(axes[0][0])
        sm = jax.shard_map(lambda b: fn(b.reshape(n, 3)).reshape(1, n, 3),
                       mesh=mesh, in_specs=in_spec, out_specs=in_spec,
                       check_vma=False)
        out = np.asarray(sm(x))
        assert np.array_equal(out, np.asarray(x).swapaxes(0,1)), (name, n)
print("OK")
""", n_devices=12)
