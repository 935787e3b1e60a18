"""``trace.py`` on a short window of the replay cell recorded on a TPU v5
lite (``testdata/replay_window.xplane.pb.gz``), and its interval
arithmetic on hand-made intervals."""
import gzip
import shutil
from pathlib import Path

import pytest

import trace
from bench import load_module

XPLANE_GZ = (Path(__file__).resolve().parents[1] / "testdata"
             / "replay_window.xplane.pb.gz")


def test_union_and_gaps():
    busy = trace.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert trace.gaps(busy, -1, 10) == [(-1, 0), (3, 5), (9, 10)]
    assert trace.clip(busy, 2, 6) == [(2, 3), (5, 6)]


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "replay_window.xplane.pb"
    with gzip.open(XPLANE_GZ, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.reduce(path, chips=1)


def test_window_and_busy(reduced):
    assert reduced["window_s"] > 0.0
    assert 0.0 < reduced["busy_s"] <= reduced["window_s"]
    assert reduced["device_busy_s"] == [reduced["busy_s"]]
    idle = sum(reduced["idle_by_span"].values())
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"],
                                 rel=1e-6)
    assert set(reduced["idle_by_span"]) <= {"call", "fetch", "sample",
                                            "none"}


def test_engine_executable_is_found(reduced):
    names = {n.split("(")[0] for n in reduced["modules"]}
    assert "jit_trial" in names
    sec, runs = trace.module_seconds(reduced, "jit_trial")
    assert runs > 0 and 0.0 < sec <= reduced["window_s"]
    eng = load_module("metrics", "replay.raptor_device_ms")
    assert eng.read({"trace": reduced}) == pytest.approx(1000.0 * sec / runs)
    idle = load_module("metrics", "device_idle.replay")
    assert 0.0 <= idle.read({"trace": reduced}) < 100.0


def test_breakdown(reduced):
    b = trace.breakdown(reduced)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all(isinstance(n, str) and v > 0.0
               for n, v in b["device_ops"] + b["idle_gaps"])
