"""chip_smoke.py refuses to run anywhere but on a TPU."""
import os
import subprocess
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent


def test_chip_smoke_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(REPO_ROOT / "chip_smoke.py")],
                         cwd=REPO_ROOT, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr and "'cpu'" in out.stderr, out.stderr
    assert '"ok"' not in out.stdout
