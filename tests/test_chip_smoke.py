"""chip_smoke.py's device gate: without an accelerator the script exits
non-zero BEFORE any leg runs, says why, and prints no result line."""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_a_cpu_backend_before_any_leg():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120, cwd=_REPO,
    )
    assert proc.returncode != 0
    assert "leg" not in proc.stdout and '"ok"' not in proc.stdout
    assert "not 'tpu'" in proc.stderr
    # it names the variable that hid the chip
    assert "JAX_PLATFORMS='cpu'" in proc.stderr
