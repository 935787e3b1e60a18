"""Closed-loop vectorized queue engine vs the scalar event-driven oracle.

The scalar FlightSim is the trusted reproduction of the paper's tables; the
batched M/G/c engine (sim/vector_queue.py) must agree with it on mean
response and failure rate for the DAG manifests (wordcount, thumbnail)
from low THROUGH high load (the task-FCFS stock rewrite closed the old
util-0.75 gap), and its dependency-masked flight scan must replay an
independent-task manifest identically to the open-loop scan it extends.

Seed convention: all randomness flows from explicit integer seeds — scalar
oracles get ``Cluster(seed=...)`` + ``FlightSim(..., seed=...)``, vector
engines ``QueueFlightSim(seed=...)`` — so every assertion reproduces
bit-for-bit from the source alone.  Scalar and vector seeds are chosen
independently (the engines share no RNG stream); agreement tolerances are
therefore statistical, sized to the windows' own run-to-run noise.
"""
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import analytics as A  # noqa: E402
from repro.sim.cluster import Cluster  # noqa: E402
from repro.sim.experiments import HA, LOW_AVAIL, rate_for  # noqa: E402
from repro.sim.flights import FlightSim  # noqa: E402
from repro.sim.vector import _flight_trial  # noqa: E402
from repro.sim.vector_queue import (QueueFlightSim, dag_flight_trial,  # noqa: E402
                                    etl_queue, keygen_queue, load_sweep,
                                    thumbnail_queue, wordcount_queue)
from repro.sim.workloads import (keygen_workload, thumbnail_workload,  # noqa: E402
                                 wordcount_workload)

JOBS, TRIALS = 1024, 16


def scalar_stats(wl_fn, *, raptor, load, seed=7, duration_s=1800.0,
                 deployment=HA):
    wl = wl_fn()
    sim = FlightSim(Cluster(seed=seed, **deployment), wl, raptor=raptor,
                    arrival_rate_hz=rate_for(wl, deployment, load),
                    duration_s=duration_s, load=load, seed=seed)
    jobs = sim.run()
    resp = np.array([j.response for j in jobs])
    return {"mean": resp.mean(), "p50": np.percentile(resp, 50),
            "p90": np.percentile(resp, 90),
            "p99": np.percentile(resp, 99),
            "fail_rate": float(np.mean([not j.ok for j in jobs]))}


# ------------------------------------------------------------------
# DAG manifests against the oracle at low AND medium load (acceptance)
# ------------------------------------------------------------------

@pytest.mark.parametrize("qwl_fn,swl_fn", [
    (wordcount_queue, wordcount_workload),
    (thumbnail_queue, thumbnail_workload),
])
@pytest.mark.parametrize("load", ["low", "medium"])
def test_dag_agrees_with_scalar(qwl_fn, swl_fn, load):
    vec = QueueFlightSim(qwl_fn(), load=load, seed=0, **HA)
    for raptor in (True, False):
        s = scalar_stats(swl_fn, raptor=raptor, load=load)
        v = vec.run(JOBS, TRIALS, raptor=raptor)
        vs = v.summary()
        assert vs["mean"] == pytest.approx(s["mean"], rel=0.08), (
            f"raptor={raptor}: scalar {s['mean']:.0f}ms "
            f"vs vector {vs['mean']:.0f}ms")
        assert v.fail_rate() == pytest.approx(s["fail_rate"], abs=0.02)


def test_dag_ratio_matches_paper_shape():
    """fig7: wordcount's storage-hop short-circuit is the big win (~0.46),
    thumbnail's data-path reuse a muted one (~0.9)."""
    wc = QueueFlightSim(wordcount_queue(), load="medium", seed=0,
                        **HA).run_pair(JOBS, TRIALS)
    th = QueueFlightSim(thumbnail_queue(), load="medium", seed=0,
                        **HA).run_pair(JOBS, TRIALS)
    assert wc["mean_ratio"] == pytest.approx(0.46, abs=0.08)
    assert th["mean_ratio"] == pytest.approx(0.92, abs=0.06)
    assert wc["mean_ratio"] < th["mean_ratio"] < 1.0


# ------------------------------------------------------------------
# the dependency-masked scan degenerates to the open-loop scan
# ------------------------------------------------------------------

def test_dag_trial_matches_open_loop_on_independent_tasks():
    """For a dep-free manifest with direct start, dag_flight_trial must
    replay byte-for-byte what sim.vector's _flight_trial replays."""
    rng = np.random.default_rng(3)
    F = K = 3
    seq = jnp.array([np.roll(np.arange(K), -m) for m in range(F)])
    dep = jnp.zeros((K, K), dtype=bool)
    f_open = jax.jit(lambda z, f, tj: _flight_trial(z, f, tj, seq, 0.5))
    f_dag = jax.jit(lambda z, f, tj: dag_flight_trial(
        z, f, tj, seq, dep, 0.5, direct_start=True))
    for trial in range(50):
        z = jnp.array(rng.exponential(900.0, (F, K)).astype(np.float32))
        fail = jnp.array(rng.random((F, K)) < 0.2)
        tj = jnp.array(rng.exponential(10.0, (F,)).astype(np.float32))
        t0, ok0 = f_open(z, fail, tj)
        t1, ok1, _ = f_dag(z, fail, tj)
        assert bool(ok0) == bool(ok1), trial
        assert float(t0) == pytest.approx(float(t1), rel=1e-6), trial


def test_dag_trial_respects_dependencies():
    """A chain manifest (a -> b -> c) can never finish faster than the sum
    of its task times, no matter the flight size."""
    rng = np.random.default_rng(5)
    K, F = 3, 3
    seq = jnp.array([[0, 1, 2]] * F)
    dep = jnp.array([[False, False, False],
                     [True, False, False],
                     [False, True, False]])
    z = jnp.array(rng.exponential(500.0, (F, K)).astype(np.float32))
    fail = jnp.zeros((F, K), dtype=bool)
    tj = jnp.zeros((F,))
    t, ok, _ = dag_flight_trial(z, fail, tj, seq, dep, 0.5)
    assert bool(ok)
    critical = sum(float(jnp.min(z[:, j])) for j in range(K))
    assert float(t) >= critical


@pytest.mark.parametrize("qwl_fn", [wordcount_queue, etl_queue])
def test_race_step_compiles_without_gathers(qwl_fn):
    """The race's event step indexes only through trace-time constants
    (member sequences, dependency mask, conditional guards), so the
    blocked replay's compiled program holds no gather inside the race:
    vmapped, each gather's small (F, K) tile costs a re-layout per step.
    ETL covers the conditional select."""
    sim = QueueFlightSim(qwl_fn(), load="medium", seed=11, block=64,
                         resolver="fixpoint", scan="seq", **HA)
    text = sim._raptor_fn(128).lower(
        sim._keys(2, True), *sim._raptor_args()).compile().as_text()
    race = [line for line in text.splitlines()
            if re.search(r'op_name="[^"]*race[^"]*"', line)]
    assert race, "the race's stage scope is missing"
    assert [line for line in race if " gather(" in line] == []


# ------------------------------------------------------------------
# queue behaviour
# ------------------------------------------------------------------

def test_response_grows_with_load():
    means = {}
    for load in ("low", "medium", "high"):
        sim = QueueFlightSim(keygen_queue(), load=load, seed=0, **HA)
        means[load] = sim.run(JOBS, 8, raptor=True).summary()["mean"]
    assert means["low"] < means["medium"] < means["high"]


def test_failure_rate_survives_queueing():
    """Error broadcast semantics are load-independent: the 1-(1-p^F)^K
    form must hold in the contended regime too."""
    sim = QueueFlightSim(keygen_queue(fail_prob=0.2), load="medium",
                         seed=0, **HA)
    r = sim.run(JOBS, TRIALS, raptor=True)
    assert r.fail_rate() == pytest.approx(
        A.raptor_failure_exact(0.2, 2), abs=0.02)
    s = sim.run(JOBS, TRIALS, raptor=False)
    assert s.fail_rate() == pytest.approx(A.forkjoin_failure(0.2, 2),
                                          abs=0.02)


@pytest.mark.parametrize("extra_passes", [0, 1])
def test_stock_taskfcfs_agrees_at_high_load(extra_passes):
    """THE tentpole regression test: wordcount STOCK at util 0.75.

    The old vector stock path admitted whole jobs FCFS in arrival order and
    read ~4x pessimistic here (ROADMAP known gap); the task-granular
    event replay must track the scalar task-level-FCFS oracle within 10%
    on mean AND p99.  Vector job count matches the scalar 1800s window so
    both see the same number of busy periods.  Covered at BOTH fixed-point
    budgets: the default (converged) and the minimal scan-over-stage-depth
    configuration the queue-stock-taskfcfs bench tier records.
    """
    s = scalar_stats(wordcount_workload, raptor=False, load="high")
    vec = QueueFlightSim(wordcount_queue(), load="high", seed=0,
                         stock_extra_passes=extra_passes, **HA)
    vs = vec.run(int(vec.rate_hz * 1800), TRIALS, raptor=False).summary()
    assert vs["mean"] == pytest.approx(s["mean"], rel=0.10), (
        f"scalar {s['mean']:.0f}ms vs vector {vs['mean']:.0f}ms")
    assert vs["p99"] == pytest.approx(s["p99"], rel=0.10), (
        f"scalar p99 {s['p99']:.0f}ms vs vector {vs['p99']:.0f}ms")


def test_saturated_regime_growth_rates_agree():
    """1-AZ/5-worker at high load is saturated BY the flights (a flight of
    2 doubles per-job worker demand => util ~1.5): backlog grows without
    bound and window means are meaningless (they scale with the window).
    Per the ROADMAP note, compare the backlog *growth rates* — the slope
    of response vs arrival time — between engines instead.
    """
    slopes = []
    for seed in (3, 11):
        wl = keygen_workload()
        sim = FlightSim(Cluster(seed=seed, **LOW_AVAIL), wl, raptor=True,
                        arrival_rate_hz=rate_for(wl, LOW_AVAIL, "high"),
                        duration_s=1800.0, load="high", seed=seed)
        jobs = sim.run()
        slopes.append(np.polyfit([j.t_arrive for j in jobs],
                                 [j.response for j in jobs], 1)[0])
    scal_slope = float(np.mean(slopes))
    vec = QueueFlightSim(keygen_queue(), load="high", seed=0, **LOW_AVAIL)
    tr = vec.trace_run(int(vec.rate_hz * 1800), 32, raptor=True)
    vec_slope = float(np.mean([
        np.polyfit(tr["arrival"][i], tr["response"][i], 1)[0]
        for i in range(tr["arrival"].shape[0])]))
    # both must actually be saturated (backlog growing)...
    assert scal_slope > 0.02 and vec_slope > 0.02
    # ...and grow at the same rate, within the regime's heavy-tailed noise
    # (the scalar slope itself moves ~10% between seeds)
    assert vec_slope == pytest.approx(scal_slope, rel=0.35), (
        f"scalar backlog slope {scal_slope:.4f} vs vector {vec_slope:.4f}")


def test_deadlocked_dag_flights_terminate_and_agree():
    """fail_prob > 0 on a staged DAG: the scalar sim used to poll a dead
    dependency forever (the event queue never drained, so the censored
    jobs could not even be observed); both engines must now terminate
    deadlocked flights with ok=False at their last event and account
    every admitted job — the shared convention the agreement tests
    depend on."""
    import dataclasses
    wl = wordcount_workload()
    wl.fail_prob = 0.35
    sim = FlightSim(Cluster(seed=3, **HA), wl, raptor=True,
                    arrival_rate_hz=rate_for(wl, HA, "low"),
                    duration_s=900.0, load="low", seed=3)
    jobs = sim.run()
    assert jobs and all(j.t_done >= 0 for j in jobs), "censored jobs"
    scal_fail = float(np.mean([not j.ok for j in jobs]))
    assert 0.2 < scal_fail < 0.9          # the regime actually deadlocks
    qwl = dataclasses.replace(wordcount_queue(), fail_prob=0.35)
    vec = QueueFlightSim(qwl, load="low", seed=0, **HA)
    r = vec.run(1024, 8, raptor=True)
    assert np.isfinite(np.asarray(r.response_ms)).all()
    assert r.fail_rate() == pytest.approx(scal_fail, abs=0.04)


def test_scalar_honors_small_stream_latency():
    """The old dependency wait polled at max(slat, 0.1)ms, quantizing
    sub-0.1ms stream latencies away from the vector scan's exact
    broadcast+slat wake (and busy-polling meanwhile).  Waits are now
    event-driven: a tiny slat runs fine and the engines agree."""
    slat = 0.02
    wl = wordcount_workload()
    sim = FlightSim(Cluster(seed=7, **HA), wl, raptor=True,
                    arrival_rate_hz=rate_for(wl, HA, "low"),
                    duration_s=1800.0, load="low",
                    stream_latency_ms=slat, seed=7)
    jobs = sim.run()
    scal_mean = float(np.mean([j.response for j in jobs]))
    vec = QueueFlightSim(wordcount_queue(), load="low", seed=0,
                         stream_latency_ms=slat, **HA)
    vs = vec.run(JOBS, TRIALS, raptor=True).summary()
    assert vs["mean"] == pytest.approx(scal_mean, rel=0.08), (
        f"slat={slat}: scalar {scal_mean:.0f}ms vs vector "
        f"{vs['mean']:.0f}ms")


def test_load_sweep_matches_single_runs():
    """The config-vmapped sweep must reproduce per-config runs exactly
    (same keys, same draws — the vmap is pure batching)."""
    sweep = load_sweep(keygen_queue(), loads=("low", "medium"), jobs=512,
                       trials=8, seed=0, **HA)
    for load in ("low", "medium"):
        solo = QueueFlightSim(keygen_queue(), load=load, seed=0,
                              **HA).run_pair(512, 8)
        assert sweep[load]["raptor"]["mean"] == pytest.approx(
            solo["raptor"]["mean"], rel=1e-4)
        assert sweep[load]["stock"]["mean"] == pytest.approx(
            solo["stock"]["mean"], rel=1e-4)
