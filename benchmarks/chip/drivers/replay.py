"""Whole-trace Monte-Carlo replay, as a capacity planner runs it.

Each call of the window replays ``trials`` trials of ``jobs`` arrivals
through the program's raptor replay engine
(``repro.sim.vector_queue.QueueFlightSim.run``) from a call seed of the
mix's pool, in the order the run's seed gives (``traffic_gen``),
and fetches every response and ``ok`` bit to the host.  Calls run back
to back for the window: JAX dispatches asynchronously, and the host
keeps ``calls_in_flight`` calls dispatched while it fetches and samples
the oldest, so a stall of the host does not leave the device idle.
``replayed_jobs_per_s`` is every job replayed and fetched over the time
from the window's start to the last fetch's end.

Which trials the reference books again is drawn from the run's seed: a
uniform sample of ``sample_trials`` of all the window's trials, kept as
the calls come (reservoir sampling).
"""
from __future__ import annotations

import collections
import gc

import numpy as np

import traffic_gen
from bench import sample_rng, span
from reference import ReplayReference, compare


def _workflow(name: str):
    from repro.sim import vector_queue
    factories = {"keygen": vector_queue.keygen_queue,
                 "wordcount": vector_queue.wordcount_queue}
    if name not in factories:
        raise ValueError(f"no replay workflow {name!r}")
    return factories[name]()


class ReplayCell:
    def __init__(self, config: dict, mix: dict, seed: int):
        if config.get("faults"):
            raise ValueError("replay cells run fault-free deployments")
        traffic_gen.check_mix(mix)
        self.config, self.mix = config, mix
        self.wl = _workflow(config["workflow"])
        self.jobs, self.trials = int(mix["jobs"]), int(mix["trials"])
        self.sample_size = int(mix["sample_trials"])
        self.in_flight = int(mix["calls_in_flight"])
        self.order = traffic_gen.pool_order(mix, seed)
        self.rng = sample_rng(seed)
        self.calls = 0          # window calls made; call 0 warms up
        self.booked = 0
        self.seen = 0
        self.samples = []       # [(call_seed, trial), resp, ok]
        self.reference = None
        self.call(0)

    def dispatch(self, index: int):
        """Start call ``index`` of the run: ``trials`` trials from its call
        seed; returns ``(call_seed, result)``."""
        from repro.sim.vector_queue import QueueFlightSim
        c, m = self.config, self.config["service_model"]
        s = traffic_gen.call_seed(self.order, index)
        with span("call"):
            sim = QueueFlightSim(
                self.wl, num_workers=c["workers"], num_azs=c["azs"],
                flight=c["flight"], rho=m["rho"], load=c["load"],
                arrival_rate_hz=float(self.mix["rate_hz"]),
                stream_latency_ms=m["stream_latency_ms"], seed=s)
            return s, sim.run(self.jobs, self.trials, raptor=True)

    @staticmethod
    def fetch(pending):
        """``(call_seed, resp, ok)`` of a dispatched call, on the host."""
        s, res = pending
        with span("fetch"):
            return s, np.asarray(res.response_ms), np.asarray(res.ok)

    def call(self, index: int):
        return self.fetch(self.dispatch(index))

    def record(self, s: int, resp: np.ndarray, ok: np.ndarray) -> None:
        """Count a window call's answers and keep its share of the
        sample."""
        self.calls += 1
        self.booked += int(np.count_nonzero(np.isfinite(resp)))
        k, n = self.sample_size, resp.shape[0]
        slots = self.rng.integers(0, self.seen + np.arange(1, n + 1))
        for t in range(n):
            item = ((s, t), resp[t].copy(), ok[t].copy())
            if self.seen + t < k:
                self.samples.append(item)
            elif slots[t] < k:
                self.samples[slots[t]] = item
        self.seen += n

    @property
    def jobs_replayed(self) -> int:
        return self.calls * self.trials * self.jobs

    def check(self) -> dict:
        """Book the sampled trials through the plain reference and
        return the compared numbers."""
        gc.collect()
        self.samples.sort(key=lambda it: it[0])
        which = [it[0] for it in self.samples]
        self.reference = ReplayReference(self.config, self.mix).run(which)
        prog = (np.stack([it[1] for it in self.samples]),
                np.stack([it[2] for it in self.samples]))
        return compare(prog, self.reference)

    def control_check(self) -> dict:
        """The control in the program's place: the reference computed in
        bfloat16, compared as the program is (after :meth:`check`)."""
        which = [it[0] for it in self.samples]
        control = ReplayReference(self.config, self.mix,
                                  "bfloat16").run(which)
        return compare(control, self.reference)


def setup(config: dict, mix: dict, seed: int, seconds: float) -> ReplayCell:
    return ReplayCell(config, mix, seed)


def window(cell: ReplayCell, seconds: float, clock,
           tick=lambda: None, loop_end=lambda: None) -> dict:
    """``tick`` is called once per turn of the loop, ``loop_end`` once
    after it."""
    gc0 = gc.get_stats()[2]["collections"]
    t0 = clock.now()
    index = 0
    pending = collections.deque()
    while True:
        while len(pending) < cell.in_flight and clock.now() - t0 < seconds:
            index += 1
            pending.append(cell.dispatch(index))
        if not pending:
            break
        tick()
        done = cell.fetch(pending.popleft())
        with span("sample"):
            cell.record(*done)
    t1 = clock.now()
    loop_end()
    return {
        "window_start": t0, "window_end": t1,
        "attempted": cell.jobs_replayed,
        "booked": cell.booked,
        "e2e": {"replayed_jobs_per_s": cell.jobs_replayed / (t1 - t0)},
        "counts": {"calls": cell.calls, "trials": cell.calls * cell.trials,
                   "jobs": cell.jobs_replayed,
                   "sampled_trials": len(cell.samples),
                   "gc_full_passes": gc.get_stats()[2]["collections"] - gc0},
    }


def check(cell: ReplayCell) -> dict:
    return cell.check()
