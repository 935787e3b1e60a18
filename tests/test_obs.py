"""The program's instrumentation (``repro.core.obs``) on the raptor replay:
the fixpoint pass counter against a plain Python count, its readings, the
host spans in a profiler trace, the compile counters, and the stage
scopes of the compiled ``jit_trial``."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import obs
from repro.sim import vector_queue as vq
from repro.sim.scan_core import booking_contrib, exclusive_running_max

JOBS, TRIALS, BLOCK, W, A = 80, 3, 16, 15, 3


def _sim(**kw):
    return vq.QueueFlightSim(vq.keygen_queue(), num_workers=W, num_azs=A,
                             load="medium", seed=11, **kw)


@pytest.fixture(scope="module")
def blocked():
    """A fixpoint run at a load high enough to queue, and the oracle."""
    sim = _sim(block=BLOCK, resolver="fixpoint", scan="seq",
               arrival_rate_hz=9.0)
    oracle = _sim(block=1, arrival_rate_hz=9.0)
    return sim, sim.run(JOBS, TRIALS), oracle.run(JOBS, TRIALS)


def _python_passes(sim, key):
    """Jacobi passes per block of one trial, counted by a plain Python
    loop that re-books a block until the rows its events observe stop
    changing."""
    wl, F = sim.wl, sim.flight
    graph = wl.graph
    seq = jnp.array(graph.member_sequences(F))
    args = sim._raptor_args()
    slat = jnp.float32(args[6])

    @jax.jit
    def draw(key, rate, rho, means, offset, cv, stage_oh, _, oh_mu,
             oh_sigma):
        k_a, k_s, k_f, k_o, k_p = jax.random.split(key, 5)
        arrivals = jnp.cumsum(
            jax.random.exponential(k_a, (JOBS,)) * (1000.0 / rate))
        return vq._raptor_job_draws(
            (k_s, k_f, k_o, k_p, None, None), arrivals, W=W, A=A, F=F,
            K=graph.K, seq=seq, dist=wl.dist, cv=cv, rho=rho, means=means,
            offset=offset, stage_oh=stage_oh, oh_mu=oh_mu,
            oh_sigma=oh_sigma, fail_prob=0.0, fault_mode=False, R=0)

    events = draw(key, *args)
    budget, closed = vq._raptor_race_budget(BLOCK, F, graph.K, False, False,
                                            True, False)
    body = jax.jit(jax.vmap(vq._raptor_job_body(
        W=W, A=A, F=F, w_az=jnp.arange(W) % A, seq=seq,
        dep_mask=jnp.array(graph.dep_mask()), slat=slat, direct=True,
        closed_form=closed, race_events=budget, fault_mode=False,
        anyfail=False, fail_prob=0.0, pol=vq.NO_RECOVERY, fp=None,
        has_failseq=False, env=None, trace=False)))
    wf = np.zeros(W, np.float32)
    counts = []
    for b in range(JOBS // BLOCK):
        ev = jax.tree_util.tree_map(
            lambda a: a[b * BLOCK:(b + 1) * BLOCK], events)
        rows = np.broadcast_to(wf, (BLOCK, W))
        p = 0
        while True:
            p += 1
            est, _ = body(jnp.asarray(rows), ev)
            new = np.asarray(exclusive_running_max(
                booking_contrib(W, *est), jnp.asarray(wf)))
            if np.array_equal(new, rows) or p == BLOCK:
                break
            rows = new
        counts.append(p)
        wf = np.maximum(wf, np.asarray(
            jnp.max(booking_contrib(W, *est), axis=0)))
    return counts


def test_pass_counter_matches_a_python_count(blocked):
    sim, res, _ = blocked
    passes = np.asarray(res.fixpoint_passes)
    assert passes.shape == (TRIALS, JOBS // BLOCK)
    assert passes.dtype == np.int32
    assert passes.min() >= 1 and passes.max() <= BLOCK
    keys = sim._keys(TRIALS, True)
    for t in (0, TRIALS - 1):
        assert passes[t].tolist() == _python_passes(sim, keys[t])
    # the load queues: some block needs more than one re-booking
    assert passes.max() > 2


def test_answers_unchanged_by_the_counter(blocked):
    _, res, oracle = blocked
    np.testing.assert_array_equal(np.asarray(res.response_ms),
                                  np.asarray(oracle.response_ms))
    np.testing.assert_array_equal(np.asarray(res.ok), np.asarray(oracle.ok))
    assert oracle.fixpoint_passes is None


def test_fixpoint_stats():
    # (calls, trials, blocks)
    p = np.array([[[1, 4, 2], [3, 4, 1]],
                  [[2, 2, 2], [1, 1, 6]]], np.int32)
    got = obs.fixpoint_stats(p)
    peaks = [3, 4, 2, 2, 2, 6]
    assert got["batched"] == pytest.approx(np.mean(peaks))
    assert got["lockstep_pct"] == pytest.approx(
        100.0 * p.sum() / (2 * sum(peaks)))
    one = obs.fixpoint_stats(p[1])
    assert one == {"batched": pytest.approx(10 / 3),
                   "lockstep_pct": pytest.approx(100.0 * 14 / 20)}
    assert obs.fixpoint_stats(np.full((4, 5), 7))["lockstep_pct"] == 100.0


def test_host_spans_share_an_id_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    _sim().run(32, 2).response_ms.block_until_ready()      # compile first
    jax.profiler.start_trace(str(tmp_path))
    try:
        sim = _sim()
        sim.run(32, 2).response_ms.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs.SPAN_PREFIX):
                    seen[ev.name] = (dict(ev.stats).get("id"),
                                     ev.start_ns, ev.duration_ns)
    assert set(seen) == {"sim.build", "sim.keys", "sim.dispatch"}
    assert {v[0] for v in seen.values()} == {sim._id}
    assert (seen["sim.build"][1] < seen["sim.keys"][1]
            < seen["sim.dispatch"][1])


def test_compile_counter_counts_a_new_shape_once():
    fn = jax.jit(lambda x: jnp.cumsum(x * 3.0) - 1.0)
    x, y, z = (jax.block_until_ready(v) for v in (
        jnp.ones(37), jnp.full(37, 2.0, jnp.float32), jnp.ones(41)))
    before = obs.compile_counts()
    fn(x).block_until_ready()
    mid = obs.compile_counts()
    fn(y).block_until_ready()
    after = obs.compile_counts()
    assert mid["process"]["compiles"] - before["process"]["compiles"] == 1
    assert mid["process"]["compile_s"] > before["process"]["compile_s"]
    assert after["process"] == mid["process"]
    # outside any sim span: nothing is put down to the program's calls
    assert after["calls"] == before["calls"]
    with obs.span("probe"):
        fn(z).block_until_ready()
    inside = obs.compile_counts()
    assert inside["calls"]["compiles"] - after["calls"]["compiles"] == 1


def test_stage_map_finds_every_stage_in_jit_trial():
    sim = _sim(block=BLOCK, resolver="fixpoint", scan="seq")
    fn = sim._raptor_fn(JOBS)
    compiled = fn.lower(sim._keys(2, True), *sim._raptor_args()).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_trial")
    stages = obs.stage_map(text)
    assert set(stages.values()) == set(obs.STAGES) | {obs.OTHER}
    # a batched loop's per-lane select does not hide the booking it holds
    fusions = {n: s for n, s in stages.items() if "fusion" in n}
    assert {"booking", "placement"} <= set(fusions.values())


def test_stage_map_rules():
    def instr(text, scope):
        return f'  {text}, metadata={{op_name="{scope}"}}'
    text = "\n".join([
        "HloModule m",
        "%fused_a (p: f32[2]) -> f32[2] {",
        instr("%x = f32[2] add(%p, %p)", "jit(t)/vmap(race)/add"),
        instr("ROOT %y = f32[2] select(%x, %x, %p)", "jit(t)/while"),
        "}",
        "ENTRY %main (a: f32[2]) -> f32[2] {",
        instr("%f = f32[2] fusion(%a), kind=kLoop, calls=%fused_a",
              "jit(t)/while"),
        instr("%g = f32[2] negate(%f)", "jit(t)/draws/booking/neg"),
        instr("%h = f32[2] negate(%g)", "jit(t)/_race_f2k2/neg"),
        "  ROOT %r = f32[2] copy(%h)",
        "}",
    ])
    m = obs.stage_map(text)
    assert m["f"] == "race"          # the scoped instruction nearest the root
    assert m["g"] == "booking"       # the innermost scope
    assert m["h"] == m["r"] == obs.OTHER
    with pytest.raises(ValueError):
        obs.stage("fetch")
