import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = ["--workload", "als_kddcup11.serve_steady", "--seed", "2147483999",
        "--seconds", "1", "--trace", "0"]


def test_no_result_line_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="x")
    p = subprocess.run([sys.executable, "benchmark/run.py", *ARGS], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr or "can find no TPU" in p.stderr


def test_no_result_line_alone_in_a_directory(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "benchmark/run.py", *ARGS], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout.strip() == ""


def test_the_child_refuses_a_cpu(tmp_path):
    report = tmp_path / "r.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmark/pio_child.py", "--report",
                        str(report), "--", "version"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 3 and p.stdout.strip() == ""
