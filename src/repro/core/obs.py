"""The program's instrumentation: host spans, compile counters, stage scopes.

* :func:`span` names a stretch of host work ``sim.<name>`` in the
  profiler's trace, on the clock the device planes use.  With no profiler
  running it costs one native call.
* Compile counters: ``jax.monitoring`` listeners, registered once at
  import, count and time jaxpr traces, backend compiles (loads from the
  persistent compile cache included: JAX times ``compile_or_get_cached``
  as a compile) and persistent-cache hits and misses.
  :func:`compile_counts` returns a snapshot, for the whole process and for
  the events that happened inside a :func:`span`, the program's own calls.
* :func:`stage` is a ``jax.named_scope``: metadata only, it leaves the
  compiled program as it was.  :func:`stage_map` reads the scopes back out
  of an optimized HLO module, so that device time per instruction can be
  summed per stage.
* :func:`fixpoint_stats` reads the replay's fixpoint pass counts
  (``QueueResult.fixpoint_passes``).
"""
from __future__ import annotations

import itertools
import re
import threading

import jax
import numpy as np

SPAN_PREFIX = "sim."
# the replay's device stages, as :func:`stage` scopes name them
STAGES = ("draws", "placement", "race", "booking")
OTHER = "other"

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_ids = itertools.count(1)
_local = threading.local()
_lock = threading.Lock()
_KEYS = ("traces", "trace_s", "compiles", "compile_s", "cache_hits",
         "cache_misses")
_counts = {"process": dict.fromkeys(_KEYS, 0),
           "calls": dict.fromkeys(_KEYS, 0)}


def next_id() -> int:
    """A new id from the process-wide counter the spans share."""
    return next(_ids)


class span:
    """``with span("dispatch", id=k):`` — the profiler's
    ``TraceAnnotation`` ``sim.dispatch`` carrying ``id=k``."""

    def __init__(self, name: str, **ids):
        self._ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **ids)

    def __enter__(self):
        _local.depth = getattr(_local, "depth", 0) + 1
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        _local.depth -= 1


def _tally(count_key, secs_key=None, secs=0.0):
    scopes = ("process", "calls") if getattr(_local, "depth", 0) else (
        "process",)
    with _lock:
        for scope in scopes:
            _counts[scope][count_key] += 1
            if secs_key:
                _counts[scope][secs_key] += secs


def _on_duration(event, secs, **_):
    if event == TRACE_EVENT:
        _tally("traces", "trace_s", secs)
    elif event == COMPILE_EVENT:
        _tally("compiles", "compile_s", secs)


def _on_event(event, **_):
    if event == CACHE_HIT_EVENT:
        _tally("cache_hits")
    elif event == CACHE_MISS_EVENT:
        _tally("cache_misses")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def compile_counts() -> dict:
    """``{"process": {...}, "calls": {...}}``: traces, compiles (with their
    seconds) and persistent-cache hits and misses so far, in the whole
    process and inside :func:`span` s."""
    with _lock:
        return {scope: dict(c) for scope, c in _counts.items()}


def fixpoint_stats(passes) -> dict:
    """Readings of fixpoint pass counts, ``(trials, blocks)`` or a stack
    of them ``(calls, trials, blocks)``: a vmapped block loop runs until
    its slowest trial converges, so ``batched`` is the mean over calls and
    blocks of the maximum over trials, and ``lockstep_pct`` the share of
    those batched passes each trial needed,
    ``100 * sum p / (trials * sum_b max_t p)``."""
    p = np.asarray(passes, dtype=np.int64)
    p = p.reshape((-1,) + p.shape[-2:])
    peak = p.max(axis=1)                           # (calls, blocks)
    return {"batched": float(peak.mean()),
            "lockstep_pct": 100.0 * float(p.sum())
            / (p.shape[1] * float(peak.sum()))}


def stage(name: str):
    """The named scope of one of the replay's :data:`STAGES`."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; known: {STAGES}")
    return jax.named_scope(name)


_STAGE_RE = re.compile(r"(?:^|[/(])(" + "|".join(STAGES) + r")(?=[/)]|$)")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")


def _scope_of(line: str):
    m = _OP_NAME_RE.search(line)
    if not m:
        return None
    found = _STAGE_RE.findall(m.group(1))
    return found[-1] if found else None


def stage_map(hlo_text: str) -> dict:
    """Instruction name -> the innermost :data:`STAGES` scope in its
    ``metadata={op_name=...}`` (``"other"`` where it has none), for every
    instruction of an optimized HLO module's text.  A fusion takes the
    scope of its fused computation's root; where the root has none (a
    batched loop's per-lane select, a multi-output tuple), that of the
    scoped instruction nearest the root, else its own."""
    scope, calls, body = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line)
        if m and " = " not in line.split("{")[0]:
            comp = m.group(1)
            body[comp] = []
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name = m.group(1)
        scope[name] = _scope_of(line)
        if comp is not None:
            body[comp].append(name)
        if " fusion(" in line:
            c = _CALLS_RE.search(line)
            if c:
                calls[name] = c.group(1)
    out = {}
    for name, s in scope.items():
        fused = [scope[i] for i in body.get(calls.get(name), ())]
        inner = next((f for f in reversed(fused) if f), None)
        out[name] = inner or s or OTHER
    return out
