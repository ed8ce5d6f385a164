"""Multi-device integration (subprocess, fake CPU devices): MoE engines,
cross-pod serdes training, elastic rescale, roofline HLO parsing."""
import pytest

from tests.conftest import run_with_devices


@pytest.mark.slow
def test_moe_engines_agree_across_mesh():
    """gather + noc engines (ALL 4 topologies) == dense oracle on a
    (data=2, model=4) mesh, with drop-free dispatch stats."""
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.models import moe as M
from repro.models.layers import init_params
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
rng = np.random.default_rng(0)
dense = M.MoEConfig(d_model=32, n_experts=8, top_k=2, d_ff=64,
                    capacity_factor=8.0, impl="dense")
params = init_params(M.moe_specs(dense), jax.random.key(0))
x = jnp.asarray(rng.normal(size=(4, 16, 32)), jnp.float32)
engines = [M.MoEConfig(32, 8, 2, 64, capacity_factor=8.0, impl="gather")]
engines += [M.MoEConfig(32, 8, 2, 64, capacity_factor=8.0, impl="noc",
                        noc_topology=t)
            for t in ("fattree", "ring", "mesh2d", "torus2d")]
with jax.set_mesh(mesh):
    ref, aux_ref, _ = M.moe_apply(params, x, dense)
    for c in engines:
        out, aux, st = M.moe_apply(params, x, c)
        tag = (c.impl, c.noc_topology)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-4, (tag, err)
        # capacity 8x => no drops => exact combine; aux equal too
        assert abs(float(aux) - float(aux_ref)) < 1e-4, tag
        assert int(st.drops) == 0 and st.fallback is None, tag
        if c.impl == "noc":
            assert st.engine == "noc" and st.topology == c.noc_topology
            assert st.rounds > 0 and st.flits > 0 and st.link_bytes > 0
print("OK")
""", n_devices=8)


@pytest.mark.slow
def test_moe_noc_ring_schedule():
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.models import moe as M
from repro.models.layers import init_params
mesh = Mesh(np.array(jax.devices()).reshape(1, 4), ("data", "model"))
rng = np.random.default_rng(1)
dense = M.MoEConfig(32, 8, 2, 64, capacity_factor=8.0, impl="dense")
ring = M.MoEConfig(32, 8, 2, 64, capacity_factor=8.0, impl="noc", noc_topology="ring")
params = init_params(M.moe_specs(dense), jax.random.key(0))
x = jnp.asarray(rng.normal(size=(2, 8, 32)), jnp.float32)
with jax.set_mesh(mesh):
    ref, _, _ = M.moe_apply(params, x, dense)
    out, _, st = M.moe_apply(params, x, ring)
assert float(jnp.max(jnp.abs(out - ref))) < 1e-4
assert st.rounds == 2 * 3   # ring(4) unidir: 3 rounds out + 3 back
print("OK")
""", n_devices=4)


@pytest.mark.slow
def test_train_serdes_pod_sync_matches_auto():
    """2-pod mesh: quasi-SERDES cross-pod gradient sync (lossless + bf16) vs
    XLA flat all-reduce."""
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.configs import get_config
from repro.core.serdes import QuasiSerdesConfig
from repro.launch.steps import make_train_step
from repro.models import transformer as T
from repro.models.layers import init_params
from repro.optim import AdamWConfig, adamw_init
mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2), ("pod", "data", "model"))
cfg = get_config("llama3.2-1b", smoke=True)
params = init_params(T.abstract_params(cfg), jax.random.key(0))
state = {"params": params, "opt": adamw_init(params)}
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)}
opt = AdamWConfig(lr=1e-3)
outs = {}
with jax.set_mesh(mesh):
    for name, kw in [("auto", dict(pod_sync="auto")),
                     ("serdes_none", dict(pod_sync="serdes",
                                          serdes=QuasiSerdesConfig(compress="none"))),
                     ("serdes_bf16", dict(pod_sync="serdes",
                                          serdes=QuasiSerdesConfig(compress="bf16")))]:
        step = make_train_step(cfg, mesh, opt, **kw)
        st2, mets = jax.jit(step)(state, batch)
        outs[name] = (float(mets["loss"]), st2["params"])
l0 = outs["auto"][0]
for name in ("serdes_none", "serdes_bf16"):
    assert abs(outs[name][0] - l0) < 1e-3, (name, outs[name][0], l0)
    d = max(float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(outs[name][1]),
                            jax.tree.leaves(outs["auto"][1])))
    tol = 1e-5 if name == "serdes_none" else 5e-3
    assert d < tol, (name, d)
print("OK")
""", n_devices=8)


@pytest.mark.slow
def test_elastic_rescale_resumes():
    """Train 4 steps on 8 devices, checkpoint, restore + reshard on 4 devices,
    continue — loss stays finite and state resharding is exact."""
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        run_with_devices(f"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.checkpoint import CheckpointConfig, CheckpointManager
from repro.configs import get_config
from repro.launch.steps import make_train_step, shardings_for_params
from repro.models import transformer as T
from repro.models.layers import init_params
from repro.optim import AdamWConfig, adamw_init
mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
cfg = get_config("llama3.2-1b", smoke=True)
params = init_params(T.abstract_params(cfg), jax.random.key(0))
state = {{"params": params, "opt": adamw_init(params)}}
rng = np.random.default_rng(0)
batch = {{"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)}}
with jax.set_mesh(mesh):
    step = jax.jit(make_train_step(cfg, mesh, AdamWConfig(lr=1e-3)))
    for _ in range(4):
        state, mets = step(state, batch)
cm = CheckpointManager(CheckpointConfig({d!r}, async_save=False))
cm.save(4, state)
print("saved", float(mets["loss"]))
""", n_devices=8)
        run_with_devices(f"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.checkpoint import CheckpointConfig, CheckpointManager
from repro.configs import get_config
from repro.launch.steps import make_train_step, shardings_for_params
from repro.models import transformer as T
from repro.models.layers import init_params
from repro.optim import AdamWConfig, adamw_init
from repro.runtime import factor_mesh
shape, axes = factor_mesh(4, prefer_model=2)
mesh = Mesh(np.array(jax.devices()).reshape(shape), axes)
cfg = get_config("llama3.2-1b", smoke=True)
proto = {{"params": init_params(T.abstract_params(cfg), jax.random.key(0))}}
proto["opt"] = __import__("repro.optim", fromlist=["adamw_init"]).adamw_init(proto["params"])
cm = CheckpointManager(CheckpointConfig({d!r}, async_save=False))
psh = shardings_for_params(cfg, mesh)
sh = {{"params": psh, "opt": {{"m": psh, "v": psh, "step": None}}}}
state, step_no, _ = cm.restore(proto, shardings=sh)
assert step_no == 4
rng = np.random.default_rng(0)
batch = {{"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)}}
with jax.set_mesh(mesh):
    step = jax.jit(make_train_step(cfg, mesh, AdamWConfig(lr=1e-3)))
    state, mets = step(state, batch)
assert np.isfinite(float(mets["loss"]))
print("resumed on 4 devices, loss", float(mets["loss"]))
""", n_devices=4)


def test_roofline_hlo_parsing():
    from repro.launch.roofline import _shape_bytes, collective_bytes
    assert _shape_bytes("bf16[128,4096]") == 128 * 4096 * 2
    assert _shape_bytes("(f32[8], u8[16])") == 48
    hlo = '''
  %ar = bf16[1024] all-reduce(%x), replica_groups={}
  %ag.1 = f32[2048] all-gather(%y), dimensions={0}
  %cp = u8[100] collective-permute(%z)
  %add = f32[4] add(%a, %b)
'''
    cb = collective_bytes(hlo)
    assert cb["all-reduce"] == 2048
    assert cb["all-gather"] == 8192
    assert cb["collective-permute"] == 100
    assert cb["n_ops"] == 3


def test_dryrun_cell_api_smoke():
    """cell_supported + input_specs wiring (the full dry-run runs offline)."""
    from repro.configs import SHAPES, get_config, input_specs
    cfg = get_config("llama3.2-1b")
    sp = input_specs(cfg, SHAPES["train_4k"])
    assert sp["tokens"].shape == (256, 4096)
    sp = input_specs(cfg, SHAPES["decode_32k"])
    assert sp["tokens"].shape == (128, 1)
    w = get_config("whisper-large-v3")
    sp = input_specs(w, SHAPES["prefill_32k"])
    assert sp["frames"].shape == (32, 1500, 128)
