"""``bench/run.py`` off a TPU: it exits non-zero and prints no result,
also from a directory that holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys


from bench.spec import ROOT, load_json

CELL = load_json(ROOT / "BENCHMARK.json")["workloads"][0]["name"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except (json.JSONDecodeError, TypeError):
            pass
    return False


def test_cpu_run_exits_nonzero_without_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    bench = load_json(ROOT / "BENCHMARK.json")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
