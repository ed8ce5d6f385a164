"""The BMVM app family's set-up and reference on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.apps import bmvm as app
from repro.apps import bmvm


@pytest.mark.parametrize("n,block", [(64, 16), (64, 64), (128, 32)])
def test_build_lut_is_preprocess(n, block):
    a, _ = app.make_inputs(app.seed_key(2 ** 31 + 3), n, 1)
    want = bmvm.preprocess(a, bmvm.BMVMConfig(n=n, k=8))
    assert np.array_equal(app.build_lut(a, 8, block), want)


def test_reference_is_gf2_product():
    a, v = app.make_inputs(app.seed_key(2 ** 40 + 11), 64, 16)
    want = (np.asarray(v, np.int64) @ np.asarray(a, np.int64).T) % 2
    assert np.array_equal(app.reference_product(a, v), want)


def test_seed_gives_the_inputs():
    a1, v1 = app.make_inputs(app.seed_key(2 ** 33 + 1), 64, 8)
    a2, v2 = app.make_inputs(app.seed_key(2 ** 33 + 1), 64, 8)
    a3, _ = app.make_inputs(app.seed_key(1), 64, 8)
    assert np.array_equal(a1, a2) and np.array_equal(v1, v2)
    # the high 32 bits of the seed count
    assert not np.array_equal(a1, a3)
    assert set(np.unique(jnp.concatenate([a1.ravel(), v1.ravel()])).tolist()) == {0, 1}
    assert a1.dtype == jax.numpy.uint8
