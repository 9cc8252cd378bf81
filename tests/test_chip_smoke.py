"""chip_smoke.py refuses to report a result without a TPU.

On the CPU (JAX_PLATFORMS=cpu, as in this suite) and in a directory that
holds the script and nothing else of the repo, it must exit non-zero and
print no ``"ok": true`` line."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu(where, tmp_path):
    script = SCRIPT
    if where == "alone":
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
