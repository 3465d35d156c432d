"""``bench/run.py`` refuses to measure without a TPU, and outside a
checkout, and prints no result then."""
import os
import shutil
import subprocess
import sys

from bench import catalog

ARGS = ["--workload", catalog.load_json(catalog.ROOT / "BENCHMARK.json")[
    "workloads"][0]["name"], "--seed", "1", "--seconds", "1", "--trace", "0"]


def _run(root, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(catalog.ROOT, {})
    assert p.returncode == 3, p.stderr
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_bench_files_alone_are_refused(tmp_path):
    shutil.copy(catalog.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(catalog.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {})
    assert p.returncode != 0 and p.stdout.strip() == ""
