"""The Pallas kernels compile for a TPU v5e chip at real widths.

Each case lowers and compiles one kernel for the first device of a described
``v5e:2x2`` topology: the TPU compiler is installed even where no chip is
attached, and it refuses what interpret mode accepts (unsupported primitives,
more SMEM or VMEM than a kernel may hold).  The compiled HLO must hold a
``tpu_custom_call``, i.e. the kernel itself and not an interpreted loop, and
the custom call must carry the kernel's ``name``, which a profile shows.

The topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and pytest-xdist workers import every
test file.
"""
import functools
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.apps import bmvm
from repro.kernels import ops as kops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gf2_bmvm import gf2_bmvm_pallas
from repro.kernels.histogram import particle_histogram_pallas
from repro.kernels.minsum import minsum_check_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compiled_hlo(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _named_kernel(hlo: str, name: str) -> bool:
    """Whether ``hlo`` holds a Mosaic custom call named after kernel ``name``."""
    return re.search(rf'^\s*(ROOT )?%{name}(\.\d+)? = .* custom-call\(.*'
                     rf'custom_call_target="tpu_custom_call"', hlo, re.M) is not None


CASES = {
    # C=512 column tiles, 2^8 partitions, R=128 words; M=1024 index words
    # are 2 MiB, twice what SMEM holds as one scalar prefetch
    "gf2_bmvm_512x256x128_m1024": (
        gf2_bmvm_pallas, [((512, 256, 128), jnp.uint32), ((1024, 512), jnp.uint32)],
        "gf2_bmvm"),
    # the benchmark's LUT at M=1: the gather, which DMAs one aligned group
    # of 8 rows a column tile out of the LUT left in HBM
    "gf2_bmvm_3072x256x3072_m1": (
        gf2_bmvm_pallas, [((3072, 256, 3072), jnp.uint32), ((1, 3072), jnp.uint32)],
        "gf2_bmvm"),
    # the benchmark's 9.66 GB LUT (n=24576, k=8) at M=128, on the stream:
    # the whole (128, 3072) output stays resident, two column tiles a step
    "gf2_bmvm_3072x256x3072_m128": (
        gf2_bmvm_pallas, [((3072, 256, 3072), jnp.uint32), ((128, 3072), jnp.uint32)],
        "gf2_bmvm"),
    # M=1024, on the stream: a resident (1024, 3072) output and the LUT
    # block overflow the 16 MiB of scoped VMEM, so the plan splits R
    "gf2_bmvm_3072x256x3072_m1024": (
        gf2_bmvm_pallas, [((3072, 256, 3072), jnp.uint32), ((1024, 3072), jnp.uint32)],
        "gf2_bmvm"),
    # pg_ldpc_H(copies=186): 1302 checks of degree 3
    "minsum_1302x3": (minsum_check_pallas, [((1302, 3), jnp.float32)], "minsum_check"),
    # a wider check degree, at an 802.11n block length
    "minsum_1296x8": (minsum_check_pallas, [((1296, 8), jnp.float32)], "minsum_check"),
    "histogram_n1024_px1024": (
        lambda b, w, r: particle_histogram_pallas(b, w, r, n_bins=16),
        [((1024, 1024), jnp.int32), ((1024,), jnp.float32), ((16,), jnp.float32)],
        "histogram"),
    "flash_attention_1x32x2048x64_bf16": (
        flash_attention_pallas, [((1, 32, 2048, 64), jnp.bfloat16)] * 3, "flash_attention"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes, name = CASES[case]
    hlo = _compiled_hlo(fn, one_chip, *shapes)
    assert "tpu_custom_call" in hlo and _named_kernel(hlo, name)


def test_iterate_kernel_names_its_kernel(one_chip, monkeypatch):
    """The app's entry at n=4096 (pack, the kernel in a scan, unpack) holds
    the kernel under its own name."""
    # on this host the dispatch picks interpret mode from the CPU backend
    monkeypatch.setattr(kops, "_interp", lambda interpret: False)
    fn = functools.partial(bmvm.iterate_kernel, cfg=bmvm.BMVMConfig(n=4096, k=8), r=1)
    hlo = _compiled_hlo(fn, one_chip, ((512, 256, 512), jnp.uint32), ((128, 4096), jnp.uint8))
    assert _named_kernel(hlo, "gf2_bmvm")


@pytest.mark.parametrize("tokens", [16, 2048])
def test_held_experts_compile_to_grouped_matmuls(tokens, one_chip):
    """The one-chip MoE layer at Jamba's published widths (d 4096, expert
    width 14336, router 16, top-2, 8 experts held), at a decode step of 16
    tokens and a prefill chunk of 2048: each projection is one grouped
    matmul that does the routed work alone (2 d f FLOPs a routed pair), not
    a dense pass of every held expert over every token."""
    from repro.models import moe as M

    c = M.MoEConfig(4096, 16, 2, 14336, n_held=8, norm_topk_prob=False)
    shapes = {"router": ((4096, 16), jnp.bfloat16),
              **{k: ((8, 4096, 14336), jnp.bfloat16) for k in ("gate", "up")},
              "down": ((8, 14336, 4096), jnp.bfloat16)}

    def layer(x, router, gate, up, down):
        params = {"router": router, "gate": gate, "up": up, "down": down}
        return M.moe_apply(params, x, c)[0]

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in [((1, tokens, 4096), jnp.bfloat16), *shapes.values()]]
    compiled = jax.jit(layer).lower(*args).compile()
    assert compiled.as_text().count("ragged-dot-metadata") >= 1
    flops = compiled.cost_analysis()
    flops = (flops[0] if isinstance(flops, list) else flops)["flops"]
    routed = 2 * tokens * 2 * 3 * 4096 * 14336
    assert routed <= flops < 1.1 * routed
