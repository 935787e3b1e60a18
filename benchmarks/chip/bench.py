"""What every cell of the benchmark shares: finding its data files by
name, seeds, host spans, the device it ran on, and the result line."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parents[1]
# scratch space of a run (the profiler's trace), inside the checkout
OUT_DIR = BENCH_DIR / ".out"


def load_json(kind: str, name: str) -> dict:
    """``<kind>/<name>.json`` under the benchmark's directory."""
    path = BENCH_DIR / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark's directory, imported by
    its path (metric names may hold dots)."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec() -> dict:
    return json.loads((CHECKOUT / "BENCHMARK.json").read_text())


def sample_rng(seed: int) -> np.random.Generator:
    """The generator of the sample the reference checks, drawn from the
    run's seed (any whole number)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), 1]))


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (``bench.<name>``)."""
    import jax
    with jax.profiler.TraceAnnotation(f"bench.{name}"):
        yield


def device_info(chips: int) -> dict:
    """The devices as JAX reports them; ``memory_peak_bytes`` is the peak
    of the fullest of the ``chips`` devices used."""
    import jax
    devs = jax.devices()[:chips]
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


class Clock:
    """Host clock, seconds."""
    now = staticmethod(time.perf_counter)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
