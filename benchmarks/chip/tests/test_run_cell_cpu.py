"""``run_cell.py`` refuses to run without the chips a cell asks for, and
without the program beside it, and prints no result line then."""
import json
import os
import shutil
import subprocess
import sys

from bench import BENCH_DIR, CHECKOUT

ARGS = ["--workload", "replay-keygen-ha", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _no_result(stdout: str) -> bool:
    for line in stdout.strip().splitlines()[-1:]:
        try:
            return "device" not in json.loads(line)
        except ValueError:
            return True
    return True


def test_exits_nonzero_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH_DIR / "run_cell.py"),
                        *ARGS], capture_output=True, text=True, env=env,
                       cwd=CHECKOUT, timeout=300)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files:
    the run fails where it would reach for the program."""
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    code = ("import sys; sys.path.insert(0, 'benchmarks/chip'); "
            "import run_cell; sys.exit(0 if run_cell.run("
            "'replay-keygen-ha', 1, 1.0, False, "
            "require_chip=False) is None else 0)")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert "No module named 'repro'" in p.stderr
    assert _no_result(p.stdout)
