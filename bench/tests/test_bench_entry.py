"""bench/run.py exits non-zero with no result line where it cannot
measure: no TPU, or a directory holding only the benchmark."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "vgg_full_128.scanned", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def _result_lines(stdout: str):
    out = []
    for line in stdout.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return out


def _run(cwd: Path, **env):
    return subprocess.run(
        [sys.executable, str(cwd / "bench" / "run.py"), *ARGS], cwd=cwd,
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


def test_no_tpu_exits_nonzero_without_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)
    assert "no TPU" in proc.stderr


def test_benchmark_alone_exits_nonzero_without_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, PYTHONPATH="")
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)
