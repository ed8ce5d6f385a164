"""Named scopes and the host span of the BMVM paths and the route rounds.

The device ops of ``iterate_kernel`` and ``iterate_spmd`` carry ``jax.named_scope``
names in their HLO metadata (``op_name``), which a profile of the chip keeps:
``bmvm.pack`` / ``bmvm.unpack``, ``kernels.gf2_bmvm`` around the kernel,
``bmvm.lookup`` / ``bmvm.reduce`` around the SPMD local work, and
``routing.all_to_all`` with ``routing.<axis>.r<t>`` for each ppermute round.
``iterate_spmd`` also opens the host span ``bmvm.spmd.build`` around its
Python work.  The multi-device cases run in a child process with 4 fake CPU
devices.
"""
import functools
import json
import re

import jax
import jax.numpy as jnp
import pytest

from repro.apps import bmvm
from tests.conftest import run_with_devices

_INSTR = re.compile(r"\s*(?:ROOT )?%?([\w.-]+) = .*? ([\w-]+)\((.*)$")
_INPUTS = ("parameter", "partition-id", "replica-id")


def hlo_ops(text: str) -> list[dict]:
    """The instructions of a compiled HLO module: name, opcode, ``op_name``
    metadata (or None), whether it sits in a fusion body, and whether it
    depends on an input (a parameter or the device's id) or only on
    constants."""
    fused = set(re.findall(r"calls=%?([\w.-]+)", text))
    ops, computation = [], None
    for line in text.splitlines():
        if line and not line[0].isspace():
            m = re.match(r"(?:ENTRY )?%?([\w.-]+)", line)
            computation = m[1] if m else None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        meta = re.search(r'op_name="([^"]*)"', line)
        operands = re.findall(r"%([\w.-]+)", m[3].split("), ")[0])
        ops.append({"name": m[1], "opcode": m[2], "op_name": meta and meta[1],
                    "operands": operands, "fused": computation in fused})
    by_name = {o["name"]: o for o in ops}
    for o in ops:
        o["input"] = o["opcode"] in _INPUTS or any(
            by_name[p]["input"] for p in o["operands"] if p in by_name)
    return ops


def scopes(op_names) -> set[str]:
    """The program's scope names among the path components of ``op_names``."""
    return {c for n in op_names if n for c in n.split("/")
            if c.startswith(("bmvm.", "kernels.", "routing."))}


def test_iterate_kernel_scopes():
    cfg = bmvm.BMVMConfig(n=64, k=8)
    lut = jax.ShapeDtypeStruct((8, 256, 8), jnp.uint32)
    v = jax.ShapeDtypeStruct((16, 64), jnp.uint8)
    run = jax.jit(functools.partial(bmvm.iterate_kernel, cfg=cfg, r=2))
    ops = hlo_ops(run.lower(lut, v).compile().as_text())
    assert {"bmvm.pack", "kernels.gf2_bmvm", "bmvm.unpack"} <= scopes(o["op_name"] for o in ops)


_SPMD = r'''
import functools, json, tempfile
from pathlib import Path
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.apps import bmvm
from repro.core import compile_routes, make_topology, run_route_program

cfg = bmvm.BMVMConfig(n=256, k=8)
rng = np.random.default_rng(5)
a = rng.integers(0, 2, (256, 256), dtype=np.uint8)
v = rng.integers(0, 2, (16, 256), dtype=np.uint8)
lut = bmvm.preprocess(a, cfg)
out = {"hlo": {}}
for topo in ("mesh", "torus", "ring", "fattree"):
    run = jax.jit(functools.partial(bmvm.iterate_spmd, cfg=cfg, r=1, topology=topo))
    out["hlo"][topo] = run.lower(lut, v).compile().as_text()
    assert np.array_equal(np.asarray(run(lut, v)), bmvm.software_ref(a, v, 1)), topo

prog = compile_routes(make_topology("mesh", 4))
mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("noc_y", "noc_x"))
spec = P(("noc_y", "noc_x"))
x = jax.device_put(jnp.zeros((16, 3), jnp.uint32), NamedSharding(mesh, spec))
route = jax.jit(jax.shard_map(lambda x: run_route_program(x, prog), mesh=mesh,
                              in_specs=spec, out_specs=spec))
out["hlo"]["route_program"] = route.lower(x).compile().as_text()

# the host span: once per eager call, once per trace under a caller's jit
mesh_run = jax.jit(functools.partial(bmvm.iterate_spmd, cfg=cfg, r=1, topology="mesh"))
with tempfile.TemporaryDirectory() as d:
    jax.profiler.start_trace(d)
    for _ in range(2):
        bmvm.iterate_spmd(lut, v, cfg, 1, topology="mesh").block_until_ready()
    with jax.profiler.TraceAnnotation("jitted"):
        for _ in range(3):
            mesh_run(lut, v).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = Path(d).glob("plugins/profile/*/*.xplane.pb")
    prof = jax.profiler.ProfileData.from_file(str(path))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for p in prof.planes
             for line in p.lines for e in line.events
             if e.name in ("bmvm.spmd.build", "jitted")]
(jitted,) = [s for s in spans if s[0] == "jitted"]
builds = [s for s in spans if s[0] == "bmvm.spmd.build"]
out["eager_builds"] = sum(1 for _, s, e in builds if e <= jitted[1])
out["jitted_builds"] = sum(1 for _, s, e in builds if s >= jitted[1])
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def spmd():
    return json.loads(run_with_devices(_SPMD, 4).strip().splitlines()[-1])


# the scopes each program must carry: the BMVM work and the rounds of its
# route program (a 2x2 mesh and torus: one round per axis; a 4-ring: 3)
ROUNDS = {
    "mesh": {"routing.noc_x.r1", "routing.noc_y.r1"},
    "torus": {"routing.noc_x.r1", "routing.noc_y.r1"},
    "ring": {"routing.noc.r1", "routing.noc.r2", "routing.noc.r3"},
    "fattree": set(),
}


@pytest.mark.parametrize("topo", sorted(ROUNDS))
def test_iterate_spmd_scopes(spmd, topo):
    ops = hlo_ops(spmd["hlo"][topo])
    want = {"bmvm.pack", "bmvm.lookup", "bmvm.reduce", "bmvm.unpack",
            "routing.all_to_all"} | ROUNDS[topo]
    assert scopes(o["op_name"] for o in ops) == want
    body = [o for o in ops if not o["fused"] and o["input"] and o["op_name"]
            and "/shard_map/" in o["op_name"]]
    assert body and [o for o in body if not scopes([o["op_name"]])] == []
    permutes = [o for o in ops if o["opcode"].startswith(("collective-permute", "all-to-all"))]
    assert permutes and all(scopes([o["op_name"]]) & (ROUNDS[topo] or {"routing.all_to_all"})
                            for o in permutes)


def test_route_program_rounds(spmd):
    """The executor's compiled route program names its rounds by the phase axis."""
    ops = hlo_ops(spmd["hlo"]["route_program"])
    permutes = [o for o in ops if o["opcode"] == "collective-permute"]
    assert {c for o in permutes for c in scopes([o["op_name"]])} == ROUNDS["mesh"]


def test_spmd_build_span(spmd):
    assert spmd["eager_builds"] == 2
    # the jitted caller traced once: later calls run the compiled program
    assert spmd["jitted_builds"] == 1


def test_lm_decode_scopes():
    """The served decode step of the hybrid model names its layers:
    ``attn.mixer``, ``mamba.mixer``, and ``moe.router`` / ``moe.experts`` /
    ``moe.combine`` in the one-chip held-experts layer."""
    from repro.configs import get_config
    from repro.launch import serve
    from repro.models import transformer as T
    from repro.models.layers import param_shapes

    cfg = get_config("ai21-jamba2-mini", smoke=True).replace(n_experts=8, experts_held=4)
    state = jax.eval_shape(lambda: serve.new_state(cfg, 2, 16))
    logits = jax.ShapeDtypeStruct((2, cfg.vocab), jnp.float32)
    hlo = serve.steps(cfg)[1].lower(param_shapes(T.abstract_params(cfg)), logits,
                                    state).compile().as_text()
    names = {c for o in hlo_ops(hlo) if o["op_name"] for c in o["op_name"].split("/")}
    assert {"attn.mixer", "mamba.mixer", "moe.router", "moe.experts", "moe.combine"} <= names
