"""The correctness check at a size a CPU test run holds.

A sound run of each cell comes out correct; the control (the reference
in bfloat16, in the program's place) and each fault planted in the
replay engine's booking come out not correct.  The runs skip the
harness's look for a chip and drive the rest of a run: set-up, window,
the freed program, the reference and the limits of the cell's file.
"""
import jax
import jax.numpy as jnp
import pytest

import run_cell
from bench import load_json
from reference import ReplayReference, compare

CELLS = ["replay-keygen-ha", "replay-wordcount-ha"]
# short trials, few of them, under the cell's own deployment and law
SMALL = {"jobs": 256, "trials": 8, "sample_trials": 6}
SECONDS = 0.5


def small_run(workload, seed=123):
    return run_cell.run(workload, seed, SECONDS, False, require_chip=False,
                        mix_override=SMALL)


def within_limits(numbers, limits):
    return all(numbers[k] <= limits[k] for k in limits)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    out = small_run(workload)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails(workload):
    cell = load_json("cells", workload)
    config = load_json("configs", cell["config"])
    mix = dict(load_json("traffic", cell["traffic"]), **SMALL)
    samples = [(77, 0), (77, 5), (78, 3)]
    ref = ReplayReference(config, mix).run(samples)
    control = ReplayReference(config, mix, "bfloat16").run(samples)
    assert not within_limits(compare(control, ref), cell["limits"])


def _break_replay(monkeypatch, fault):
    """Plant ``fault`` in the replay engine's blocked booking."""
    from repro.sim import vector_queue
    replay = vector_queue.blocked_event_replay

    def unchanged(job_body, w0, events, **kw):
        # every job books against the idle cluster: the carried
        # free-at state never changes
        return replay(lambda wf, inp: job_body(jnp.zeros_like(wf), inp),
                      w0, events, **kw)

    def half(job_body, w0, events, **kw):
        arr = events[0]
        dead = jnp.arange(arr.shape[0]) >= arr.shape[0] // 2
        return replay(job_body, w0,
                      (jnp.where(dead, jnp.inf, arr),) + events[1:], **kw)

    def altered(job_body, w0, events, **kw):
        wf, (resp, ok) = replay(job_body, w0, events, **kw)
        return wf, (resp.at[0].add(1.0), ok)

    monkeypatch.setattr(vector_queue, "blocked_event_replay",
                        {"unchanged": unchanged, "half": half,
                         "altered": altered}[fault])
    _fresh_engines()


def _fresh_engines():
    from repro.sim import vector_queue
    vector_queue._raptor_runner.cache_clear()
    vector_queue._raptor_trial_fn.cache_clear()
    jax.clear_caches()


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_fault_in_the_timed_path_fails(monkeypatch, workload, fault):
    _break_replay(monkeypatch, fault)
    try:
        out = small_run(workload, seed=321)
    finally:
        monkeypatch.undo()
        _fresh_engines()
    assert out["correct"] is False
