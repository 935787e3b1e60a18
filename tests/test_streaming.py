"""Streaming scheduler service: composition exactness + traffic bank.

The streaming engine's one load-bearing claim is COMPOSITION: N
microbatched steps over a persistent W-state are bitwise one whole-trace
``blocked_event_replay`` of the concatenated event stream.  The tests pin
that on runs AND traces — plain, fail_prob>0, and the full fault branch
(brownouts + crashes + timeout/retry/hedge policy) — across microbatch
sizes, blocked configs, and ragged (padded) tails.  The traffic-bank
tests check the arrival processes' laws (resumability, rate, burstiness,
diurnal phase) and the heavy-tail service family; the M/M/c test anchors
the service's steady-state mean sojourn to queueing theory at low
utilisation.

Seed convention: explicit integer seeds everywhere, as in
tests/test_sim_queue.py — every assertion reproduces from source alone.
"""
import math

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.sim.cluster import OverheadModel, lognormal_params  # noqa: E402
from repro.sim.events import (DiurnalArrivals, MMPPArrivals,  # noqa: E402
                              PoissonArrivals)
from repro.sim.faults import FaultProfile  # noqa: E402
from repro.sim.policies import RecoveryPolicy  # noqa: E402
from repro.sim.streaming import (StreamingScheduler, oracle_check,  # noqa: E402
                                 run_open_load, stock_open_sojourns)
from repro.sim.vector import unit_draws  # noqa: E402
from repro.sim.vector_queue import (QueueFlightSim,  # noqa: E402
                                    exponential_queue, heavytail_queue,
                                    keygen_queue, wordcount_queue)
from repro.sim.workloads import UTIL, arrival_rate_hz  # noqa: E402

FAULTS = FaultProfile(az_mtbf_ms=4_000.0, az_mttr_ms=400.0,
                      degraded_inflation=1.6, degraded_fail_prob=0.08,
                      crash_mtbf_ms=30_000.0, crash_restart_ms=200.0)
POLICY = RecoveryPolicy(timeout_ms=2_500.0, max_retries=1,
                        backoff_ms=20.0, hedge_ms=1_500.0)


# ---------------------------------------------------------------------------
# composition: N streamed microbatches == one whole-trace replay (bitwise)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block,microbatch", [(1, 16), (8, 16), (16, 5)])
def test_streamed_equals_whole_trace_runs(block, microbatch):
    sim = QueueFlightSim(keygen_queue(), num_workers=12, num_azs=3,
                         load="medium", seed=3, block=block)
    res = oracle_check(sim, n_steps=4, microbatch=microbatch)
    assert res["bitwise"], res


def test_streamed_equals_whole_trace_traces():
    sim = QueueFlightSim(keygen_queue(), num_workers=12, num_azs=3,
                         load="high", seed=4, block=8)
    res = oracle_check(sim, n_steps=3, microbatch=12, trace=True)
    assert res["bitwise"], res
    # every trace column individually, not just the conjunction
    for col in ("resp", "ok", "arrival", "dispatch", "worker", "release"):
        assert res[col], (col, res)


def test_streamed_equals_whole_trace_failprob():
    sim = QueueFlightSim(keygen_queue(fail_prob=0.08), num_workers=9,
                         num_azs=3, load="medium", seed=6, block=8)
    res = oracle_check(sim, n_steps=3, microbatch=10, trace=True)
    assert res["bitwise"], res


def test_streamed_equals_whole_trace_faults_on():
    sim = QueueFlightSim(keygen_queue(), num_workers=9, num_azs=3,
                         load="high", seed=5, block=4,
                         faults=FAULTS, recovery=POLICY)
    res = oracle_check(sim, n_steps=3, microbatch=10, trace=True)
    assert res["bitwise"], res


def test_streamed_dag_manifold():
    sim = QueueFlightSim(wordcount_queue(), num_workers=15, num_azs=3,
                         load="medium", seed=2, block=8)
    res = oracle_check(sim, n_steps=3, microbatch=8)
    assert res["bitwise"], res


def test_padded_tail_leaves_wstate_untouched():
    """A padded (inf-arrival) slot books nothing: the W-state after a
    padded microbatch is bitwise the state after replaying only its live
    prefix (truncate the engine's own drawn event tensors — padding sits
    at the end, so the live prefix is exactly events[:6])."""
    from repro.sim.vector_queue import _raptor_stream_fns
    sim = QueueFlightSim(keygen_queue(), num_workers=8, num_azs=2,
                         load="medium", seed=9, block=1)
    arr = PoissonArrivals(sim.rate_hz, seed=1).take(6)
    eng = StreamingScheduler(sim, microbatch=16, keep_events=True, seed=0)
    eng.submit(arr)
    eng.drain()
    events = eng.concatenated_events()
    truncated = jax.tree_util.tree_map(lambda x: x[:6], events)
    _, _, step = _raptor_stream_fns(
        sim.W, sim.A, sim.flight, sim.wl.graph,
        sim.wl.dist, sim.wl.fail_prob, sim._fp, sim._policy,
        1, "fixpoint", "seq", sim.summary_backend, False)
    wf_live, _ = step(jnp.zeros(sim.W), truncated, eng.env, sim.slat)
    np.testing.assert_array_equal(np.asarray(eng.wf), np.asarray(wf_live))


def test_streaming_monotone_submit_validation():
    sim = QueueFlightSim(keygen_queue(), num_workers=8, num_azs=2, seed=0)
    eng = StreamingScheduler(sim, microbatch=8)
    with pytest.raises(ValueError):
        eng.submit(np.array([5.0, 3.0]))          # unsorted
    with pytest.raises(ValueError):
        eng.submit(np.zeros((2, 2)))              # not 1-D
    with pytest.raises(ValueError):
        eng.submit(np.arange(9, dtype=float))     # overflows microbatch
    with pytest.raises(ValueError):
        StreamingScheduler(sim, microbatch=0)
    with pytest.raises(ValueError):
        StreamingScheduler(sim, pipeline_depth=0)


# ---------------------------------------------------------------------------
# M/M/c sanity: steady-state mean sojourn at low utilisation
# ---------------------------------------------------------------------------

def _erlang_c_wait_ms(lam_per_ms, svc_ms, c):
    a = lam_per_ms * svc_ms                 # offered load (erlangs)
    rho = a / c
    pterms = [a ** k / math.factorial(k) for k in range(c)]
    p_full = (a ** c / (math.factorial(c) * (1 - rho)))
    C = p_full / (sum(pterms) + p_full)     # Erlang-C delay probability
    return C * svc_ms / (c * (1 - rho))


def test_mmc_mean_sojourn_low_util():
    """flight=1, single exp task, rho=1.0 (pure AZ-shared draw => exactly
    exponential service): mean sojourn ~= E[oh] + E[S] + Erlang-C wait."""
    mean_ms = 1000.0
    wl = exponential_queue(num_tasks=1, mean_ms=mean_ms, flight=1)
    sim = QueueFlightSim(wl, num_workers=8, num_azs=1, load="low",
                         rho=1.0, seed=11)
    rep = run_open_load(sim, jobs=6000, microbatch=256, warmup=False,
                        process=PoissonArrivals(sim.rate_hz, seed=3),
                        seed=1)
    mu, sigma = lognormal_params(*OverheadModel.TABLE[(False, "low")])
    e_oh = math.exp(mu + sigma * sigma / 2)
    svc = mean_ms + wl.raptor_stage_ms + e_oh   # worker occupancy per job
    lam = sim.rate_hz / 1000.0                  # per ms
    want = e_oh + mean_ms + wl.raptor_stage_ms + _erlang_c_wait_ms(
        lam, svc, sim.W)
    assert rep.ok_frac == 1.0
    assert abs(rep.mean_ms - want) / want < 0.08, (rep.mean_ms, want)


# ---------------------------------------------------------------------------
# arrival processes: law + resumability
# ---------------------------------------------------------------------------

def test_poisson_take_resumes_the_stream():
    p = PoissonArrivals(50.0, seed=1)
    a, b = p.take(400), p.take(600)
    q = PoissonArrivals(50.0, seed=1)
    np.testing.assert_allclose(np.r_[a, b], q.take(1000))
    assert np.all(np.diff(np.r_[a, b]) >= 0)
    p.reset()
    np.testing.assert_allclose(p.take(400), a)


def test_mmpp_rate_and_burstiness():
    rate = 80.0
    m = MMPPArrivals(rate, burst_factor=8.0, dwell_s=(5.0, 1.0), seed=2)
    x = m.take(60_000)
    measured = 1000.0 * x.size / x[-1]
    assert abs(measured - rate) / rate < 0.05
    # index of dispersion of 100ms-window counts: Poisson -> 1, MMPP >> 1
    cnt = np.histogram(x, bins=np.arange(0.0, x[-1], 100.0))[0]
    iod = cnt.var() / cnt.mean()
    assert iod > 3.0, iod
    pois = PoissonArrivals(rate, seed=2).take(60_000)
    pcnt = np.histogram(pois, bins=np.arange(0.0, pois[-1], 100.0))[0]
    assert iod > 3.0 * pcnt.var() / pcnt.mean()


def test_diurnal_phase_modulation():
    d = DiurnalArrivals(100.0, amplitude=0.6, period_s=10.0, seed=3)
    y = d.take(60_000)
    measured = 1000.0 * y.size / y[-1]
    assert abs(measured - 100.0) / 100.0 < 0.05
    # rising half of the sinusoid (phase [0, 0.5)) must carry more
    # arrivals than the falling half, in the analytic proportion
    ph = (y % d.period_ms) / d.period_ms
    hi = np.mean(ph < 0.5)
    # integral of (1 + a sin(2 pi u)) over [0, .5] = .5 + a/pi
    want_hi = 0.5 + 0.6 / np.pi
    assert abs(hi - want_hi) < 0.02, (hi, want_hi)


def test_arrival_validation():
    with pytest.raises(ValueError):
        PoissonArrivals(0.0)
    with pytest.raises(ValueError):
        PoissonArrivals(float("inf"))
    with pytest.raises(ValueError):
        MMPPArrivals(10.0, burst_factor=0.9)
    with pytest.raises(ValueError):
        MMPPArrivals(10.0, dwell_s=(1.0, -2.0))
    with pytest.raises(ValueError):
        DiurnalArrivals(10.0, amplitude=1.0)
    with pytest.raises(ValueError):
        DiurnalArrivals(10.0, period_s=0.0)
    with pytest.raises(ValueError):
        PoissonArrivals(10.0).take(-1)


# ---------------------------------------------------------------------------
# heavy-tail service family + workload validation
# ---------------------------------------------------------------------------

def test_pareto_unit_draws_mean_and_tail():
    cv = 2.0
    x = np.asarray(unit_draws(jax.random.PRNGKey(0), (200_000,),
                              "pareto", cv))
    assert abs(x.mean() - 1.0) < 0.05
    # heavier tail than exp at matched mean: the power law only separates
    # deep in the tail — P(X > 15) is ~8e-4 for pareto(cv=2) but ~3e-7
    # for exp(1) (0.06 expected draws in 200k)
    e = np.asarray(unit_draws(jax.random.PRNGKey(1), (200_000,), "exp", 1.0))
    assert np.mean(x > 15.0) > 4e-4
    assert np.mean(e > 15.0) < 1e-4
    alpha = 1.0 + math.sqrt(1.0 + 1.0 / (cv * cv))
    assert (x >= (alpha - 1.0) / alpha - 1e-6).all()   # support floor xm


def test_heavytail_queue_streams_bitwise():
    sim = QueueFlightSim(heavytail_queue(cv=2.0), num_workers=10,
                         num_azs=2, load="medium", seed=8, block=8)
    res = oracle_check(sim, n_steps=3, microbatch=10)
    assert res["bitwise"], res


def test_heavytail_factory_validation():
    with pytest.raises(ValueError):
        heavytail_queue(dist="weibull")
    with pytest.raises(ValueError):
        heavytail_queue(cv=0.0)


def test_arrival_rate_hz_validation():
    assert arrival_rate_hz(2.0, 10, "medium") == UTIL["medium"] * 10 / 2.0
    with pytest.raises(ValueError, match="unknown load"):
        arrival_rate_hz(2.0, 10, "extreme")
    with pytest.raises(ValueError):
        arrival_rate_hz(0.0, 10, "medium")
    with pytest.raises(ValueError):
        arrival_rate_hz(2.0, 0, "medium")


# ---------------------------------------------------------------------------
# the sustained-load driver + stock reference
# ---------------------------------------------------------------------------

def test_run_open_load_report_fields():
    sim = QueueFlightSim(keygen_queue(), num_workers=12, num_azs=3,
                         load="medium", seed=1)
    rep = run_open_load(sim, jobs=300, microbatch=64, warmup=True,
                        process=MMPPArrivals(sim.rate_hz, seed=4), seed=2)
    assert rep.jobs == 300
    assert rep.jobs_per_s > 0 and rep.wall_s > 0
    assert rep.p50_ms <= rep.p99_ms
    assert 0.0 <= rep.slo_violation_frac <= 1.0
    assert rep.horizon_ms > 0 and rep.offered_rate_hz > 0
    with pytest.raises(ValueError):
        run_open_load(sim, jobs=0)


def test_stock_open_sojourns_dep_free_only():
    sim = QueueFlightSim(keygen_queue(), num_workers=12, num_azs=3,
                         load="low", seed=1)
    arr = PoissonArrivals(sim.rate_hz, seed=5).take(400)
    resp = stock_open_sojourns(sim, arr, seed=0)
    assert resp.shape == (400,) and (resp > 0).all()
    wsim = QueueFlightSim(wordcount_queue(), num_workers=15, num_azs=3,
                          load="low", seed=1)
    with pytest.raises(ValueError, match="dep-free"):
        stock_open_sojourns(wsim, arr)


# ---------------------------------------------------------------------------
# the service face and its launcher
# ---------------------------------------------------------------------------

def test_scheduler_service_runs_at_its_own_settings():
    """run_open_load books at the service's microbatch and seed, not at
    fresh defaults: the same load through the module driver at those
    settings gives the same sojourns."""
    from repro.serving.engine import SchedulerService
    sim = QueueFlightSim(keygen_queue(), num_workers=12, num_azs=3,
                         load="medium", seed=1)
    svc = SchedulerService(sim, microbatch=24, pipeline_depth=1, seed=7)
    got = svc.run_open_load(jobs=200, warmup=False,
                            process=PoissonArrivals(sim.rate_hz, seed=3))
    want = run_open_load(sim, jobs=200, microbatch=24, pipeline_depth=1,
                         seed=7, warmup=False,
                         process=PoissonArrivals(sim.rate_hz, seed=3))
    other = run_open_load(sim, jobs=200, warmup=False,
                          process=PoissonArrivals(sim.rate_hz, seed=3))
    assert (got.mean_ms, got.p99_ms) == (want.mean_ms, want.p99_ms)
    assert got.mean_ms != other.mean_ms
    assert svc.config == sim.engine_config("raptor")


@pytest.fixture
def cache_config():
    """Restore the persistent-cache settings the launcher turns on, so no
    later test in this process writes a cache."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def test_launcher_scheduler_mode_reports_device_and_config(capsys,
                                                         cache_config):
    """The device and the resolved engine config come before the numbers;
    the last line is the whole report as JSON."""
    import json

    from repro.launch import serve
    from repro.sim.vector_queue import auto_config
    assert serve.main(["--mode", "scheduler", "--workload", "keygen",
                       "--workers", "9", "--azs", "3", "--jobs", "128",
                       "--microbatch", "32", "--arrival", "mmpp"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    dev = jax.devices()[0]
    assert lines[0].startswith(
        f"device {dev.platform} {dev.device_kind} x{jax.device_count()}")
    blk, res, scan = auto_config("raptor")
    assert f"block={blk} resolver={res} scan={scan}" in lines[0]
    rep = json.loads(lines[-1])
    assert rep["jobs"] == 128 and rep["microbatch"] == 32
    assert (rep["block"], rep["resolver"], rep["scan"]) == (blk, res, scan)
    assert rep["platform"] == dev.platform and rep["flight"] == 2
    assert rep["compile_cold_s"] > 0 and rep["compile_warm_s"] > 0
    assert rep["p50_ms"] <= rep["p99_ms"]


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir, cache_config):
    """JAX_COMPILATION_CACHE_DIR wins and no other directory is set in
    code; without it the cache is the fixed path inside the checkout."""
    from repro.launch.compile_cache import (CHECKOUT_CACHE_DIR,
                                            enable_compile_cache)
    if env_dir:
        # as JAX itself does when the variable is set at start-up
        given = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", given)
        jax.config.update("jax_compilation_cache_dir", given)
        assert enable_compile_cache() == given
        assert jax.config.jax_compilation_cache_dir == given
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(
            CHECKOUT_CACHE_DIR)
        assert (CHECKOUT_CACHE_DIR.parent / "chip_smoke.py").exists()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
