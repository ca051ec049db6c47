"""Without a TPU the entry point fails and prints no result."""
import os
import shutil
import subprocess
import sys

import pytest

import run


def _run(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lubm.broad_open",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
    )


def test_refuses_without_a_tpu():
    p = _run(run.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_no_chip_is_detected_before_any_work():
    with pytest.raises(run.NoChip):
        run.require_chips(1)
