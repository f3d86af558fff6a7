"""``bench/run.py`` prints no result and exits non-zero where it cannot
measure: on a machine whose JAX has no TPU, and in a checkout that holds
only the benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "cu16k_nve", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def run(cwd, env_extra):
    env = {**os.environ, **env_extra}
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(stdout):
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_exits_nonzero_without_a_tpu():
    p = run(ROOT, {"JAX_PLATFORMS": "cpu",
                   "PYTHONPATH": os.path.join(ROOT, "src")})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert no_result(p.stdout)


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run(str(tmp_path), {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert p.returncode != 0
    assert no_result(p.stdout)
