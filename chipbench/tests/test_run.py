"""``run.py`` does no work and prints no result where it cannot measure:
on a host whose JAX finds no TPU, and in a checkout that holds only the
benchmark's own files."""
import os
import shutil
import subprocess
import sys

from chipbench.tests import fixtures

ARGS = ["--workload", "criteo-emb.cs_adam.cat0", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "chipbench/run.py"] + ARGS,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_tpu_no_result():
    p = _run(fixtures.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copytree(fixtures.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(fixtures.REPO / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_ignores_bench_run():
    p = _run(fixtures.REPO, {"BENCH_RUN": "anything"})
    assert p.returncode != 0 and p.stdout.strip() == ""
