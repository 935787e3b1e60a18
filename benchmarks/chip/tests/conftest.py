"""Harness tests run on the CPU: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/chip/tests`` from the checkout's root."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(1, str(BENCH_DIR.parents[1] / "src"))
