"""chip_smoke.py's refusals: off a TPU, or short of devices, it exits
non-zero and prints no result line.  (What it does ON the chip is
proved by running it there; ``--rehearse`` walks the same phases at toy
sizes on the CPU and is too slow for this tier.)"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(cwd, script, *args):
    # one CPU device: not the eight virtual ones conftest.py asks for
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="", XLA_FLAGS="")
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_refuses_to_run_off_a_tpu():
    proc = _run(REPO, SMOKE)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_four_chips_need_four_devices():
    proc = _run(REPO, SMOKE, "--chips", "4", "--rehearse")
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert '"ok"' not in proc.stdout
    assert "needs 4 devices" in proc.stderr
