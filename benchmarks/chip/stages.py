#!/usr/bin/env python3
"""The replay engine's work read from inside the program: fixpoint passes
and lockstep, device time per stage, host spans, compile counts.

    python3 benchmarks/chip/stages.py --workload <cell> --seed <n> \\
        [--calls <k>] [--save <dir>]

Sets a replay cell up as ``run_cell.py`` does (its warm-up call
included), then makes ``k`` calls untimed by the profiler and ``k`` more
under it, each batch ``calls_in_flight`` deep as in the cell's window,
and prints one JSON line:

* ``fixpoint_passes``, ``pass_lockstep_pct``: the traced calls'
  ``QueueResult.fixpoint_passes`` read by ``repro.core.obs.fixpoint_stats``;
* ``stage_ms``: device self time per call of the ``jit_trial`` operations
  in each stage scope (``repro.core.obs.stage_map`` of the runner's
  optimized HLO, compiled again at the cell's shapes after the calls) and
  ``other``; loop containers (``while``, ``conditional``, ``call``) hold
  their bodies' time and are left out;
* ``host_ms``: host time per call in the program's ``sim.build``,
  ``sim.keys`` and ``sim.dispatch`` spans;
* ``compile``: the program's compile seconds before the calls, and the
  traces and compiles counted during them (expected none);
* ``jobs_per_s``: both batches, and ``stage_map_compile_s``, the cost of
  the stage map.

``--save`` keeps the trace and the stage map.  Needs a TPU: without one
it exits with code 3.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import gzip
import json
import re
import shutil
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(1, str(BENCH_DIR.parents[1] / "src"))

from bench import (OUT_DIR, Clock, load_json, load_module,  # noqa: E402
                   span)

NO_CHIP = 3
EXECUTABLE = "jit_trial"
CONTAINER = re.compile(r"\b(?:while|conditional|call)\(")


def _op_name(event_name: str) -> str:
    return event_name.split(" = ")[0].lstrip("%")


def reduce_stages(path, stage_map: dict, device: int = 0,
                  executable: str = EXECUTABLE) -> dict:
    """Device seconds per stage of ``executable``'s operations, the
    executable's own seconds and runs, and host seconds per ``sim.*``
    span, from one profiler trace (``.xplane.pb``)."""
    from jax.profiler import ProfileData

    from repro.core import obs
    runs, ops, spans = [], [], collections.defaultdict(list)
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == f"/device:TPU:{device}":
            for line in plane.lines:
                if line.name == "XLA Modules":
                    runs = sorted(
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events
                        if ev.name.split("(")[0] == executable)
                elif line.name == "XLA Ops":
                    ops = [(ev.start_ns, ev.duration_ns, ev.name)
                           for ev in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(obs.SPAN_PREFIX):
                        spans[ev.name[len(obs.SPAN_PREFIX):]].append(
                            ev.duration_ns * 1e-9)
    stage_s = dict.fromkeys(obs.STAGES + (obs.OTHER,), 0.0)
    other_ops = collections.Counter()
    unmapped = 0
    starts = [s for s, _ in runs]
    for start, dur, name in ops:
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start >= runs[i][1] or CONTAINER.search(name):
            continue
        op = _op_name(name)
        if op not in stage_map:
            unmapped += 1
        stage = stage_map.get(op, obs.OTHER)
        stage_s[stage] += dur * 1e-9
        if stage == obs.OTHER:
            other_ops[op] += dur * 1e-9
    return {"runs": len(runs),
            "module_s": sum(e - s for s, e in runs) * 1e-9,
            "stage_s": stage_s,
            "unmapped_ops": unmapped,
            "other_ops": other_ops.most_common(8),
            "spans": {n: {"count": len(v), "s": sum(v)}
                      for n, v in spans.items()}}


def _batch(cell, first: int, calls: int):
    """``calls`` calls from index ``first``, ``in_flight`` deep; returns
    (seconds from the first dispatch to the last fetch, pass counts)."""
    t0 = Clock.now()
    pending, passes, index = collections.deque(), [], first
    while index < first + calls or pending:
        while len(pending) < cell.in_flight and index < first + calls:
            pending.append(cell.dispatch(index))
            index += 1
        done = pending.popleft()
        cell.fetch(done)
        passes.append(done[1].fixpoint_passes)
    return Clock.now() - t0, passes


def measure(workload: str, seed: int, calls: int, save=None, *,
            require_chip: bool = True, mix_override: dict = None):
    cell_spec = load_json("cells", workload)
    config = load_json("configs", cell_spec["config"])
    mix = dict(load_json("traffic", cell_spec["traffic"]),
               **(mix_override or {}))
    import jax
    if require_chip and jax.default_backend() != "tpu":
        return None
    from repro.core import obs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.sim.vector_queue import QueueFlightSim
    enable_compile_cache()
    driver = load_module("drivers", mix["driver"])
    cell = driver.ReplayCell(config, mix, seed)          # warm-up call 0
    before = obs.compile_counts()
    plain_s, _ = _batch(cell, 1, calls)
    trace_dir = OUT_DIR / "stages" / workload
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with span("traced"):
            traced_s, passes = _batch(cell, 1 + calls, calls)
    finally:
        jax.profiler.stop_trace()
    during = obs.compile_counts()
    c, m = config, config["service_model"]
    sim = QueueFlightSim(
        cell.wl, num_workers=c["workers"], num_azs=c["azs"],
        flight=c["flight"], rho=m["rho"], load=c["load"],
        arrival_rate_hz=float(mix["rate_hz"]),
        stream_latency_ms=m["stream_latency_ms"], seed=0)
    t0 = Clock.now()
    hlo = sim._raptor_fn(cell.jobs).lower(
        sim._keys(cell.trials, True), *sim._raptor_args()).compile().as_text()
    stage_map = obs.stage_map(hlo)
    map_s = Clock.now() - t0
    import trace as trace_mod
    xplane = trace_mod.find_xplane(trace_dir)
    red = reduce_stages(xplane, stage_map)
    if save:
        save = Path(save)
        save.mkdir(parents=True, exist_ok=True)
        with open(xplane, "rb") as src, gzip.open(
                save / f"{workload}.xplane.pb.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
        with gzip.open(save / f"{workload}.stage_map.json.gz", "wt") as f:
            json.dump(stage_map, f, sort_keys=True)
    shutil.rmtree(trace_dir, ignore_errors=True)
    runs = max(red["runs"], 1)
    own = sum(red["stage_s"].values())
    n_dispatch = red["spans"].get("dispatch", {}).get("count", 0) or 1
    host = {n: 1e3 * v["s"] / n_dispatch for n, v in red["spans"].items()}
    stats = (obs.fixpoint_stats(jax.device_get(passes))
             if passes and passes[0] is not None else {})
    jobs = calls * cell.trials * cell.jobs
    return {
        "workload": workload, "seed": seed, "calls": calls,
        "fixpoint_passes": stats.get("batched"),
        "pass_lockstep_pct": stats.get("lockstep_pct"),
        "jit_trial_ms": 1e3 * red["module_s"] / runs,
        "jit_trial_runs": red["runs"],
        "self_ms": 1e3 * own / runs,
        "stage_ms": {k: 1e3 * v / runs for k, v in red["stage_s"].items()},
        "other_pct": 100.0 * red["stage_s"]["other"] / own if own else None,
        "other_ops": red["other_ops"], "unmapped_ops": red["unmapped_ops"],
        "host_ms": host, "host_dispatch_ms": sum(host.values()),
        "compile": {"program_compile_s": before["calls"]["compile_s"],
                    "program_compiles": before["calls"]["compiles"],
                    "cache_hits": before["process"]["cache_hits"],
                    "cache_misses": before["process"]["cache_misses"],
                    "traces_during_calls": (during["process"]["traces"]
                                            - before["process"]["traces"]),
                    "compiles_during_calls": (
                        during["process"]["compiles"]
                        - before["process"]["compiles"])},
        "jobs_per_s": {"untraced": jobs / plain_s, "traced": jobs / traced_s},
        "stage_map_compile_s": map_s,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)
    out = measure(args.workload, args.seed, args.calls, args.save)
    if out is None:
        print("stages: needs a TPU", file=sys.stderr)
        return NO_CHIP
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
