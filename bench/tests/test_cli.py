"""The command refuses to measure anything but a TPU, and a checkout
that holds only the benchmark's files cannot run at all."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from bench.harness import ROOT


def run_cli(cwd, root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         "cloud-scan.uniform.open", "--seed", str(2 ** 31 + 3),
         "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except ValueError:
            pass
    return True


def test_no_tpu_exits_nonzero_without_result():
    p = run_cli(ROOT, ROOT)
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_cli(tmp_path, tmp_path)
    assert p.returncode != 0
    assert no_result(p.stdout)
