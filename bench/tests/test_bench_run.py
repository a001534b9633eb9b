"""``bench/run.py`` off a TPU, and in a checkout without the program:
a non-zero exit and no result line."""
import json
import os
import shutil
import subprocess
import sys

from bench.harness import spec

ARGS = ["--workload", "qwen_chat", "--seed", str(2**40 + 1), "--seconds",
        "1", "--trace", "0"]


def run_in(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, os.path.join(root, "bench",
                                                         "run.py")] + ARGS,
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
            return False
        except ValueError:
            continue
    return True


def test_refuses_the_cpu_with_no_result():
    p = run_in(spec.ROOT)
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "needs a TPU" in p.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = run_in(str(tmp_path))
    assert p.returncode != 0
    assert no_result(p.stdout)
