"""End-to-end training integration on the host devices (1 CPU)."""
import os
import subprocess
import sys

import numpy as np
import pytest

from tests.conftest import SRC

_CACHE_PROBE = """
import sys, uuid
import jax, jax.numpy as jnp
from repro.launch.cache import use_compile_cache
print(use_compile_cache())
# a constant no other program holds gives this compile a fresh cache key
c = uuid.uuid4().int % 1_000_003
jax.jit(lambda x: x * c + 1).lower(jnp.ones(3)).compile()
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(tmp_path, from_env):
    """The entry points' cache goes where JAX_COMPILATION_CACHE_DIR says, and
    to the repository's fixed .jax_cache only when it is unset."""
    from repro.launch.cache import REPO_CACHE_DIR

    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=SRC + os.pathsep + env.get("PYTHONPATH", ""))
    want = tmp_path / "cache" if from_env else REPO_CACHE_DIR
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    before = set(os.listdir(want)) if want.is_dir() else set()
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(want)]
    assert set(os.listdir(want)) - before, "no cache entry written"


def test_tiny_training_loss_decreases():
    from repro.launch.train import run
    losses = run(["--arch", "llama3.2-1b", "--smoke", "--steps", "120",
                  "--batch", "8", "--seq", "32", "--lr", "2e-3",
                  "--log-every", "60"])
    first = np.mean(losses[:10])
    last = np.mean(losses[-10:])
    assert last < first - 0.1, (first, last)


def test_serve_driver_batched_requests():
    from repro.launch.serve import run
    out = run(["--arch", "llama3.2-1b", "--smoke", "--requests", "6",
               "--batch", "3", "--prompt-len", "16", "--gen", "4"])
    assert out.shape == (6, 4)
    assert (out >= 0).all()


def test_train_with_checkpoint_restart(tmp_path):
    from repro.launch.train import run
    d = str(tmp_path / "ck")
    run(["--arch", "llama3.2-1b", "--smoke", "--steps", "6", "--batch", "4",
         "--seq", "16", "--ckpt", d, "--ckpt-every", "3"])
    # resume picks up from the checkpoint and continues to 10
    losses = run(["--arch", "llama3.2-1b", "--smoke", "--steps", "10",
                  "--batch", "4", "--seq", "16", "--ckpt", d, "--ckpt-every", "5"])
    assert len(losses) >= 4
