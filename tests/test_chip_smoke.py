"""chip_smoke.py rehearsed on the CPU: every phase at a tiny size (the Pallas
kernels in interpret mode), the four-chip phase on four fake host devices,
and the script itself, which must refuse to run without a TPU."""
import os
import subprocess
import sys

import pytest

import chip_smoke as cs
from tests.conftest import REPO, run_with_devices

PHASES = {
    "bmvm": lambda: cs.phase_bmvm(n=64, k=8, m=24, r=3, seed=0, kernel_marker=None),
    "ldpc": lambda: cs.phase_ldpc(copies=8, batch=16, iters=6, snr_db=2.0, seed=0,
                                  kernel_marker=None),
    "pf": lambda: cs.phase_pf(img=64, roi=16, n_particles=64, n_bins=16, frames_n=4,
                              seed=0, kernel_marker=None),
    "noc": lambda: cs.phase_noc(seed=0),
    "serve": lambda: cs.phase_serve("llama3.2-1b", smoke=True, requests=4, batch=2,
                                    prompt=16, gen=4, seed=0),
    "train": lambda: cs.phase_train("llama3.2-1b", steps=3, batch=4, seq=16, seed=0),
}


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_phase_at_tiny_size(phase, capsys):
    PHASES[phase]()
    assert f"[{phase}]" in capsys.readouterr().out


def test_four_chip_phase_on_fake_devices():
    out = run_with_devices(f"""
import sys
sys.path.insert(0, {REPO!r})
import chip_smoke as cs
cs.phase_four_chips(n=64, m=8, r=2, seed=0)
""", 4)
    assert "[four-chips] 4 cpu devices" in out


def test_script_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout and "no TPU" in out.stderr
