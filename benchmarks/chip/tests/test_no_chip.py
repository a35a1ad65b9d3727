"""Without a TPU the run prints no result and exits non-zero; so does a
checkout that holds only BENCHMARK.json and the benchmark's files."""
import os
import shutil
import subprocess
import sys

from benchmarks.chip import common

CMD = ["--workload", "gpp-si214.back_to_back", "--seed", "3000000000",
       "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "benchmarks/chip/run_cell.py",
                           *CMD], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(common.ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(common.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(common.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert not p.stdout.strip()
