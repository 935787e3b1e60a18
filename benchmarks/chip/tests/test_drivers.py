"""The replay driver's loop and arithmetic at a tiny size, on fakes:
window accounting, job counts and the sample the reference books; the
metric readers; the traffic's key schedule."""
import jax
import numpy as np
import pytest

import traffic_gen
from bench import load_module


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def now(self):
        return self.t


def fake_cell(drv, clock, call_s, trials=4, jobs=8, sample=3, seed=5,
              in_flight=2):
    """A replay cell whose every call takes ``call_s`` of the clock to
    fetch and answers response ``call seed + trial / 100`` for each
    job."""
    cell = drv.ReplayCell.__new__(drv.ReplayCell)
    cell.jobs, cell.trials, cell.sample_size = jobs, trials, sample
    cell.in_flight = in_flight
    cell.order = traffic_gen.pool_order({"pool_seed": 100,
                                         "pool_calls": 1000}, seed)
    cell.rng = np.random.default_rng(seed)
    cell.calls = cell.booked = cell.seen = 0
    cell.samples = []

    def dispatch(index):
        return traffic_gen.call_seed(cell.order, index), None

    def fetch(pending):
        clock.t += call_s
        s = pending[0]
        resp = np.repeat((s + np.arange(trials) / 100.0)[:, None], jobs, 1)
        return s, resp.astype(np.float64), np.ones((trials, jobs), bool)

    cell.dispatch, cell.fetch = dispatch, fetch
    return cell


@pytest.mark.parametrize("in_flight", [1, 2, 4])
def test_window_accounting(in_flight):
    drv = load_module("drivers", "replay")
    clock = FakeClock()
    # binary fractions of a second, so the clock adds up exactly
    cell = fake_cell(drv, clock, 1 / 64, in_flight=in_flight)
    res = drv.window(cell, 10 / 64, clock)
    # ten fetches fill the window; the calls in flight when it closes
    # are fetched and counted too, each call 4 trials of 8 jobs
    calls = 10 + in_flight - 1
    counts = dict(res["counts"])
    assert counts.pop("gc_full_passes") >= 0
    assert counts == {"calls": calls, "trials": 4 * calls,
                      "jobs": 32 * calls, "sampled_trials": 3}
    assert res["attempted"] == res["booked"] == 32 * calls
    assert res["window_end"] - res["window_start"] == calls / 64
    assert res["e2e"]["replayed_jobs_per_s"] == pytest.approx(32 * 64)


def test_window_counts_unbooked_jobs():
    drv = load_module("drivers", "replay")
    clock = FakeClock()
    cell = fake_cell(drv, clock, 1 / 64)
    fetch = cell.fetch

    def lossy(pending):
        s, resp, ok = fetch(pending)
        resp[0, :2] = np.inf
        return s, resp, ok

    cell.fetch = lossy
    res = drv.window(cell, 4 / 64, clock)
    assert res["counts"]["calls"] == 5
    assert res["attempted"] - res["booked"] == 5 * 2


def test_sample_is_drawn_from_the_seed_and_matches_its_answers():
    drv = load_module("drivers", "replay")
    picks = []
    for _ in range(2):
        clock = FakeClock()
        cell = fake_cell(drv, clock, 1 / 64, sample=5)
        drv.window(cell, 30 / 64, clock)
        picks.append(sorted(it[0] for it in cell.samples))
        for (s, t), resp, ok in cell.samples:
            assert np.all(resp == s + t / 100.0) and ok.all()
    assert picks[0] == picks[1] and len(set(picks[0])) == 5
    clock = FakeClock()
    other = fake_cell(drv, clock, 1 / 64, sample=5, seed=6)
    drv.window(other, 30 / 64, clock)
    assert sorted(it[0] for it in other.samples) != picks[0]


def test_sample_is_uniform_over_the_window():
    drv = load_module("drivers", "replay")
    counts = np.zeros(44)
    for seed in range(300):
        clock = FakeClock()
        cell = fake_cell(drv, clock, 1 / 64, sample=4, seed=seed)
        drv.window(cell, 10 / 64, clock)
        for (s, t), _, _ in cell.samples:
            c = list(cell.order).index(s) - 1
            counts[4 * c + t] += 1
    # 1200 picks over the 11 calls' 44 trials: 27 each on average
    assert counts.min() > 10 and counts.max() < 55


def test_idle_metric_reads_the_trace_share():
    idle = load_module("metrics", "device_idle.replay")
    assert idle.read({"trace": {"busy_s": 3.0, "window_s": 4.0}}) == 25.0
    assert idle.read({"trace": None}) is None


def test_engine_metric_needs_the_executable():
    eng = load_module("metrics", "replay.raptor_device_ms")
    t = {"modules": {"jit_trial(1)": 0.5, "jit__threefry_split": 0.1},
         "module_runs": {"jit_trial(1)": 10, "jit__threefry_split": 10}}
    assert eng.read({"trace": t}) == 50.0
    t = {"modules": {"other": 1.0}, "module_runs": {"other": 1}}
    assert eng.read({"trace": t}) is None


def test_arrivals_follow_the_seed():
    mix = {"pool_seed": 1000, "pool_calls": 7}
    order = traffic_gen.pool_order(mix, 2**33 + 5)
    keys = traffic_gen.trial_keys(traffic_gen.call_seed(order, 4), 16)
    streams = []
    for key in keys:
        k_a = traffic_gen.split_trial_key(key)[0]
        a = np.asarray(traffic_gen.poisson_arrivals(k_a, 2048, 3.55))
        b = np.asarray(traffic_gen.poisson_arrivals(k_a, 2048, 3.55))
        assert a.dtype == np.float32 and np.array_equal(a, b)
        assert np.all(np.diff(a) >= 0.0)
        streams.append(a)
    rate = 16 * 2048 / sum(a[-1] / 1000.0 for a in streams)
    assert rate == pytest.approx(3.55, rel=0.03)
    other = traffic_gen.trial_keys(traffic_gen.call_seed(order, 5), 16)
    assert not np.array_equal(jax.random.key_data(keys),
                              jax.random.key_data(other))


def test_every_seed_replays_the_pool_in_its_own_order():
    mix = {"pool_seed": 1000, "pool_calls": 7}
    orders = [traffic_gen.pool_order(mix, s) for s in (2**33 + 5, 6)]
    again = traffic_gen.pool_order(mix, 2**33 + 5)
    assert np.array_equal(orders[0], again)
    assert not np.array_equal(orders[0], orders[1])
    for order in orders:
        seeds = [traffic_gen.call_seed(order, c) for c in range(14)]
        assert sorted(seeds[:7]) == list(range(1000, 1007))
        assert seeds[7:] == seeds[:7]


def test_mix_is_checked():
    ok = {"arrival": "poisson", "rate_hz": 1.0, "jobs": 1, "trials": 1,
          "pool_calls": 4, "pool_seed": 1000}
    traffic_gen.check_mix(ok)
    for bad in ({"arrival": "mmpp"}, {"rate_hz": 0.0}, {"pool_calls": 0},
                {"pool_seed": 2**30}):
        with pytest.raises(ValueError):
            traffic_gen.check_mix(dict(ok, **bad))
