"""``stages.py`` and the ``setup.compile_s`` metric: the stage reduction on
a short traced batch of the keygen replay cell recorded on a TPU v5 lite
with the program's ``sim.*`` spans (``testdata/replay_stages.*``: the
profiler's trace and the stage map of that ``jit_trial``), and a run of
the whole measurement at a tiny size on the CPU."""
import gzip
import json
import shutil
import sys
from pathlib import Path

import pytest

import stages
import trace
from bench import load_module

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
SMALL = {"jobs": 64, "trials": 4, "sample_trials": 2, "calls_in_flight": 2,
         "pool_calls": 3}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("stages") / "replay_stages.xplane.pb"
    with gzip.open(TESTDATA / "replay_stages.xplane.pb.gz", "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(TESTDATA / "replay_stages.stage_map.json.gz", "rt") as f:
        stage_map = json.load(f)
    return path, stage_map


def test_stage_split_of_jit_trial(recorded):
    from repro.core import obs
    path, stage_map = recorded
    red = stages.reduce_stages(path, stage_map)
    assert red["runs"] >= 1 and red["unmapped_ops"] == 0
    split = red["stage_s"]
    assert set(split) == set(obs.STAGES) | {obs.OTHER}
    assert all(split[s] > 0.0 for s in obs.STAGES)
    own = sum(split.values())
    # the leaf operations tile the executable's runs, loops left out
    assert 0.9 * red["module_s"] < own <= red["module_s"]
    assert split[obs.OTHER] < 0.15 * own


def test_same_executable_as_the_engine_metric(recorded):
    path, stage_map = recorded
    red = stages.reduce_stages(path, stage_map)
    sec, runs = trace.module_seconds(trace.reduce(path), "jit_trial")
    assert runs == red["runs"]
    assert sec == pytest.approx(red["module_s"], rel=1e-6)


def test_host_spans_per_call(recorded):
    path, stage_map = recorded
    spans = stages.reduce_stages(path, stage_map)["spans"]
    assert set(spans) == {"build", "keys", "dispatch"}
    counts = {v["count"] for v in spans.values()}
    assert len(counts) == 1 and counts.pop() >= 1
    assert all(v["s"] > 0.0 for v in spans.values())


def test_measure_on_the_cpu_at_a_tiny_size():
    out = stages.measure("replay-keygen-ha", 8000000001, 2,
                         require_chip=False, mix_override=SMALL)
    assert set(out["host_ms"]) == {"build", "keys", "dispatch"}
    assert out["host_dispatch_ms"] == pytest.approx(
        sum(out["host_ms"].values()))
    assert out["compile"]["compiles_during_calls"] == 0
    assert out["compile"]["program_compiles"] >= 1
    assert out["jobs_per_s"]["untraced"] > 0.0
    # no TPU plane on the CPU: no device stage times to read
    assert out["jit_trial_runs"] == 0


def test_compile_metric_reads_the_program_counter(monkeypatch):
    metric = load_module("metrics", "setup.compile_s")
    from repro.core import obs
    got = metric.read({"trace": None, "counts": {}})
    assert got == obs.compile_counts()["calls"]["compile_s"] >= 0.0
    # a program without the counters: nothing to read, no error
    import repro.core
    monkeypatch.delattr(repro.core, "obs")
    monkeypatch.setitem(sys.modules, "repro.core.obs", None)
    assert metric.read({"trace": None, "counts": {}}) is None
