#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 benchmarks/chip/run_cell.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell (``cells/<cell>.json``) names a configuration
(``configs/<config>.json``), a traffic mix (``traffic/<mix>.json``), the
chips it needs and the limits of its correctness check; the mix names
its driver (``drivers/<driver>.py``).  Set-up (``setup_s``) runs from the
process's start to the window's start: imports, device start-up, the
compile cache, the cell's deployment and data from ``--seed``, and one
warm-up of the cell's shapes.  The window then runs for ``--seconds``.
With ``--trace 1`` the profiler records the last ``TRACE_SECONDS`` of
the window's loop (a whole window's trace runs to hundreds of MB) and
the line carries the cell's per-layer metrics
(``metrics/<metric>.py``) read from it and a breakdown; with
``--trace 0`` its end-to-end metrics.  After the window,
the program's state is freed and what it produced is compared with the
plain reference; the compared numbers and their limits end standard
error and the result line.

The run needs a TPU with as many chips as the cell asks for: without one
it exits with code 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(1, str(BENCH_DIR.parents[1] / "src"))

from bench import (OUT_DIR, Clock, benchmark_spec, device_info,  # noqa
                   load_json, load_module, log)

NO_CHIP = 3
TRACE_SECONDS = 2.0


class Tracer:
    """Profiles the last ``seconds`` of the window's loop: ``tick`` is
    called by the driver once per turn of its loop and starts the
    profiler when that much of the window is left; ``stop`` ends it.
    The traced part is the ``bench.traced`` span.  The Python tracer is
    off: it slows every Python call of the host path."""

    def __init__(self, trace_dir: Path, window: float, seconds: float):
        self.dir = trace_dir
        self.start_after = max(0.0, window - seconds)
        self.t0 = self.span = None
        self.done = False

    def tick(self):
        if self.t0 is None:
            self.t0 = Clock.now()
        if (self.span is None and not self.done
                and Clock.now() - self.t0 >= self.start_after):
            import jax
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation("bench.traced")
            self.span.__enter__()

    def stop(self):
        if self.span is not None:
            import jax
            self.span.__exit__(None, None, None)
            self.span = None
            jax.profiler.stop_trace()
        self.done = True


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _number(v):
    if isinstance(v, float) and not math.isfinite(v):
        return str(v)
    return v


def metric_names(spec: dict, section: str, workload: str):
    """The metrics of ``section`` that this cell reports."""
    return [m for m in spec[section]
            if "workloads" not in m or workload in m["workloads"]]


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, mix_override: dict = None) -> dict:
    """One run of one cell; returns the result line as a dict, or
    ``None`` where the device does not fit the cell.  Tests run it
    without a chip, on a smaller mix."""
    cell = load_json("cells", workload)
    config = load_json("configs", cell["config"])
    mix = dict(load_json("traffic", cell["traffic"]), **(mix_override or {}))
    spec = benchmark_spec()
    chips = int(cell["chips"])

    marks = [("files", Clock.now())]
    import jax
    marks.append(("import_jax", Clock.now()))
    if require_chip:
        devs = jax.devices()
        if jax.default_backend() != "tpu" or len(devs) < chips:
            log(f"run_cell: the cell needs {chips} TPU chip(s); JAX found "
                f"{len(devs)} {jax.default_backend()} device(s)")
            return None
        marks.append(("devices", Clock.now()))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    driver = load_module("drivers", mix["driver"])
    marks.append(("cache_and_driver", Clock.now()))
    state = driver.setup(config, mix, seed, seconds)
    marks.append(("program_cell_warmup", Clock.now()))
    trace_dir = OUT_DIR / "trace" / workload
    hooks = {}
    if trace:
        tracer = Tracer(trace_dir, seconds, TRACE_SECONDS)
        hooks = {"tick": tracer.tick, "loop_end": tracer.stop}
    try:
        res = driver.window(state, seconds, Clock, **hooks)
    finally:
        if trace:
            tracer.stop()
    setup_s = res["window_start"] - T_START
    device = device_info(chips)
    reduced = None
    if trace:
        import trace as trace_mod
        try:
            reduced = trace_mod.reduce(trace_mod.find_xplane(trace_dir),
                                       chips)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    numbers = driver.check(state)
    limits = cell["limits"]
    correct = all(numbers[k] <= limits[k] for k in limits)
    failed = int(res["attempted"] - res["booked"])

    metrics = {}
    if trace:
        ctx = {"trace": reduced, "counts": res["counts"]}
        for m in metric_names(spec, "per_layer", workload):
            v = load_module("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(res["e2e"], setup_s=setup_s)
        for m in metric_names(spec, "end_to_end", workload):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": int(res["attempted"]),
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = trace_mod.breakdown(reduced)
    out["checks"] = {k: {"value": _number(numbers[k]), "limit": limits[k]}
                     for k in limits}
    t_prev, phases = T_START, {}
    for name, t in marks:
        phases[name] = t - t_prev
        t_prev = t
    log(f"setup_phases_s {json.dumps(phases)}")
    log(f"counts {json.dumps(res['counts'])}")
    for k in limits:
        log(f"check {k} {numbers[k]!r} limit {limits[k]!r}")
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    if out is None:
        return NO_CHIP
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
