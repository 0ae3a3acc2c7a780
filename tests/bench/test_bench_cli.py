"""``bench/run.py`` without a chip: it exits non-zero and prints no
result, in this checkout and in one that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

import _bench_path


def run(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "danube.chat",
         "--seed", str(2 ** 31 + 99), "--seconds", "1", "--trace", "0",
         *args], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    proc = run(_bench_path.REPO)
    assert proc.returncode != 0
    assert "Nothing was run" in proc.stderr
    assert "{" not in proc.stdout


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(_bench_path.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(_bench_path.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run(str(tmp_path))
    assert proc.returncode != 0
    assert "{" not in proc.stdout
