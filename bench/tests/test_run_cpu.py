"""The command refuses to measure off a TPU: exit code 2, no result."""
import json
import os
import shutil
import subprocess
import sys

import harness

RUN = os.path.join(harness.BENCH, "run.py")


def run(cwd, script=RUN):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, "--workload",
                           "train.yi-9b-4L.hbfp8", "--seed", str(2**33),
                           "--seconds", "1", "--trace", "0"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
            return False
        except ValueError:
            pass
    return True


def test_no_tpu_no_result():
    r = run(harness.ROOT)
    assert r.returncode == 2 and no_result(r.stdout)
    assert "needs a TPU" in r.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = run(tmp_path, str(tmp_path / "bench" / "run.py"))
    assert r.returncode != 0 and no_result(r.stdout)


def test_unknown_workload_gives_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, RUN, "--workload", "nope",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 2 and no_result(r.stdout)
