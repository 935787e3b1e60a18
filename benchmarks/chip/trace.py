"""Reduction of a profiler trace to the benchmark's numbers.

``jax.profiler`` writes an ``.xplane.pb``; :func:`reduce` reads it with
JAX's own ``ProfileData`` and returns, for the traced window:

* ``window_s``: the length of the harness's ``bench.traced`` host span,
  the traced part of the window;
* ``busy_s``: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  clipped to the window and averaged over the chips used;
* ``device_busy_s``: that union per chip, for imbalance across chips;
* ``modules``: device seconds and runs per executable (the ``XLA
  Modules`` line), averaged over the chips used;
* ``device_ops``: device seconds per operation (the HLO instruction's
  name, the text before `` = ``; a loop's time holds its body's),
  averaged likewise;
* ``idle_by_span``: the device's idle seconds inside the window, each
  idle interval split over the harness's host spans (``bench.<name>``)
  that overlap it, the rest under ``"none"``; averaged over chips.

Host and device timestamps share the profiler's clock.
"""
from __future__ import annotations

import bisect
import collections
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "traced"


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals):
    """Sorted, merged ``[(start, end)]`` of the given intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy, lo, hi):
    """Complement of merged ``busy`` inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.duration_ns)


def read_planes(path):
    """``(devices, spans)``: per device id, its op intervals and module
    and op events; host spans as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = list(_events(line))
                elif line.name == MODULES_LINE:
                    dev["modules"] = list(_events(line))
            devices[int(plane.name[len(DEVICE_PREFIX):].split()[0])] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for name, s, d in _events(line):
                    if name.startswith(SPAN_PREFIX):
                        spans.append((name[len(SPAN_PREFIX):], s, s + d))
    return devices, spans


def reduce(path, chips: int = 1) -> dict:
    devices, spans = read_planes(path)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench.traced span")
    lo, hi = windows[0]
    used = sorted(devices)[:chips]
    if not used:
        raise ValueError("the trace holds no TPU device plane")
    # the harness's spans run one after another on one thread, so their
    # ends are sorted as their starts are
    others = sorted((e, s, n) for n, s, e in spans if n != WINDOW_SPAN
                    and e > lo and s < hi)
    ends = [e for e, _, _ in others]
    busy_s, modules, ops = [], collections.Counter(), collections.Counter()
    runs = collections.Counter()
    idle = collections.Counter()
    for d in used:
        dev = devices[d]
        in_win = [(s, s + du) for _, s, du in dev["ops"]]
        busy = union(clip(in_win, lo, hi))
        busy_s.append(sum(e - s for s, e in busy) * 1e-9)
        for name, s, du in dev["modules"]:
            if s + du > lo and s < hi:
                modules[name] += du * 1e-9
                runs[name] += 1
        for name, s, du in dev["ops"]:
            if s + du > lo and s < hi:
                ops[name.split(" = ")[0]] += du * 1e-9
        for g0, g1 in gaps(busy, lo, hi):
            covered = 0.0
            i = bisect.bisect_right(ends, g0)
            while i < len(others) and others[i][1] < g1:
                e, s, n = others[i]
                ov = min(e, g1) - max(s, g0)
                if ov > 0:
                    idle[n] += ov * 1e-9
                    covered += ov
                i += 1
            if g1 - g0 > covered:
                idle["none"] += (g1 - g0 - covered) * 1e-9
    k = float(len(used))
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_s) / k,
        "device_busy_s": busy_s,
        "modules": {n: v / k for n, v in modules.items()},
        "module_runs": {n: v / k for n, v in runs.items()},
        "device_ops": {n: v / k for n, v in ops.items()},
        "idle_by_span": {n: v / k for n, v in idle.items()},
    }


def module_seconds(reduced: dict, prefix: str):
    """``(seconds, runs)`` of the executables whose name starts with
    ``prefix``; ``(0.0, 0)`` where none ran."""
    sec = sum(v for n, v in reduced["modules"].items()
              if n.startswith(prefix))
    runs = sum(v for n, v in reduced["module_runs"].items()
               if n.startswith(prefix))
    return sec, runs


def breakdown(reduced: dict, top: int = 10) -> dict:
    def top_of(d):
        return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": top_of(reduced["device_ops"]),
            "idle_gaps": top_of(reduced["idle_by_span"])}
