"""Bring-up check: the Raptor scheduler's main paths on a TPU, end to end.

    python chip_smoke.py             # phases a-d on one chip
    python chip_smoke.py --chips 4   # phase e only: the sharded sweeps

Phases, all in this one process (a chip belongs to one process):

a. the scheduling service through its launcher
   (``repro.launch.serve --mode scheduler``) on the paper's HA deployment,
   15 workers over 3 AZs at flight 2: keygen under MMPP arrivals, then
   wordcount (the DAG dependency path) under Poisson arrivals;
b. the same service on a 1024-worker fleet over 3 AZs;
c. exactness on the chip: the streamed bookings replayed whole-trace
   through the ``block=1`` oracle (faults off and on), and the whole-trace
   engines at the backend's auto config against ``block=1``, bit for bit;
d. the two sim-side Pallas kernels compiled (``interpret=False``) against
   their references, alone and inside the stock engine;
e. the device-sharded sweeps on four chips against one, bit for bit.

Each phase prints one ``phase <name>: {json}`` line.  The last line is
``{"ok": true, "device": {...}}`` only when every phase passed on a TPU;
anything else exits non-zero without it.  The compile cache is
``repro.launch.compile_cache``'s.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

HA = dict(num_workers=15, num_azs=3)


def report(name, **fields):
    print(f"phase {name}: {json.dumps(fields)}", flush=True)
    return fields


# -- a, b: the service through its launcher ----------------------------------

def serve(name, workload, *, workers, azs, arrival, jobs, microbatch):
    """One launcher run; checks the report is whole and plausible."""
    from repro.launch import serve as launcher
    argv = ["--mode", "scheduler", "--workload", workload, "--load",
            "medium", "--workers", str(workers), "--azs", str(azs),
            "--arrival", arrival, "--jobs", str(jobs), "--microbatch",
            str(microbatch), "--seed", "0"]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = launcher.main(argv)
    text = out.getvalue()
    print(text, end="", file=sys.stderr)
    rep = json.loads(text.strip().splitlines()[-1])
    ok = (rc == 0 and rep["jobs"] == jobs and rep["flight"] == 2
          and rep["ok_frac"] == 1.0
          and 0.0 < rep["p50_ms"] <= rep["p99_ms"] < float("inf")
          and rep["jobs_per_s"] > 0.0)
    return report(name, ok=ok, **{k: rep[k] for k in (
        "jobs", "jobs_per_s", "p50_ms", "p99_ms", "mean_ms", "ok_frac",
        "offered_rate_hz", "compile_cold_s", "compile_warm_s", "block",
        "resolver", "scan", "workers", "azs", "flight", "microbatch")})


def phase_service(jobs_keygen=16384, jobs_wordcount=8192):
    return [serve("a/keygen-ha-mmpp", "keygen", workers=15, azs=3,
                  arrival="mmpp", jobs=jobs_keygen, microbatch=64),
            serve("a/wordcount-ha-poisson", "wordcount", workers=15, azs=3,
                  arrival="poisson", jobs=jobs_wordcount, microbatch=64)]


def phase_fleet(workers=1024, jobs=65536):
    return [serve("b/keygen-fleet-poisson", "keygen", workers=workers,
                  azs=3, arrival="poisson", jobs=jobs, microbatch=256)]


# -- c: exactness on the chip -------------------------------------------------

def fault_setting():
    """The fault environment and recovery policy the property tests pin
    (tests/test_queue_properties.py FAULTS/POLICY)."""
    from repro.sim.faults import FaultProfile
    from repro.sim.policies import RecoveryPolicy
    return dict(
        faults=FaultProfile(az_mtbf_ms=24_000.0, az_mttr_ms=6_000.0,
                            degraded_inflation=2.0, degraded_fail_prob=0.05,
                            crash_mtbf_ms=300_000.0,
                            crash_restart_ms=2_000.0),
        recovery=RecoveryPolicy(timeout_ms=6_000.0, max_retries=1,
                                backoff_ms=50.0, backoff_jitter=0.5,
                                hedge_ms=2_500.0))


ENGINES = ("raptor", "stock")


def runs(sim, jobs, trials, engines=ENGINES):
    """The engines' raw outputs, timed: the first call compiles."""
    out = {}
    for engine in engines:
        t0 = time.perf_counter()
        r = sim.run(jobs, trials, raptor=engine == "raptor")
        resp, ok = jax.device_get((r.response_ms, r.ok))
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(sim.run(jobs, trials,
                                      raptor=engine == "raptor").response_ms)
        out[engine] = dict(resp=resp, ok=ok, result=r, cold_s=cold,
                           warm_s=time.perf_counter() - t0)
    return out


def same(a, b):
    return bool(np.array_equal(a["resp"], b["resp"], equal_nan=True)
                and np.array_equal(a["ok"], b["ok"]))


def whole_trace_vs_oracle(name, wl, jobs, trials, oracle=None,
                          engines=ENGINES, stock_kernel=False, **sim_kw):
    """``sim_kw``'s engines against the block=1 oracle, bit for bit; the
    oracle's runs are returned for reuse by later comparisons.
    ``stock_kernel``: also require a compiled Pallas kernel in the stock
    engine's program."""
    from repro.sim.vector_queue import QueueFlightSim
    sim = QueueFlightSim(wl, load="medium", seed=0, **HA, **sim_kw)
    fields = {}
    if stock_kernel:
        fields["tpu_custom_call"] = lowered_has_kernel(
            sim._stock_fn(jobs), sim._keys(trials, False),
            *sim._stock_args())
    if oracle is None:
        oracle = runs(QueueFlightSim(wl, load="medium", seed=0, block=1,
                                     **HA), jobs, trials)
    got = runs(sim, jobs, trials, engines)
    for engine in engines:
        g = got[engine]
        s = g["result"].summary()
        fields[engine] = dict(
            config=list(sim.engine_config(engine)),
            bitwise=same(g, oracle[engine]), mean_ms=s["mean"],
            p99_ms=s["p99"], n_failed=s["n_failed"], cold_s=g["cold_s"],
            warm_s=g["warm_s"], jobs_per_s=jobs * trials / g["warm_s"])
    if engines == ENGINES:
        fields["mean_ratio"] = (fields["raptor"]["mean_ms"]
                                / fields["stock"]["mean_ms"])
    ok = (all(fields[e]["bitwise"] for e in engines)
          and fields.get("tpu_custom_call", True))
    return report(name, ok=ok, jobs=jobs, trials=trials, **fields), oracle


def phase_exact(keygen_jobs=4096, keygen_trials=256, wc_jobs=2048,
                wc_trials=64, fleet_workers=1024):
    from repro.sim.streaming import oracle_check
    from repro.sim.vector_queue import (QueueFlightSim, keygen_queue,
                                        wordcount_queue)
    out = []
    for label, kw in (("faults-off", {}), ("faults-on", fault_setting())):
        sim = QueueFlightSim(keygen_queue(), load="medium", seed=0, **HA,
                             **kw)
        res = oracle_check(sim, n_steps=8, microbatch=64, trace=True)
        out.append(report(f"c/stream-oracle-{label}", ok=res["bitwise"],
                          config=list(sim.engine_config("raptor")),
                          columns=res))
    sim = QueueFlightSim(keygen_queue(), num_workers=fleet_workers,
                         num_azs=3, load="medium", seed=0)
    res = oracle_check(sim, n_steps=6, microbatch=256, trace=True)
    out.append(report("c/stream-oracle-fleet", ok=res["bitwise"],
                      workers=fleet_workers,
                      config=list(sim.engine_config("raptor")),
                      columns=res))
    r, _ = whole_trace_vs_oracle("c/keygen-auto-vs-block1", keygen_queue(),
                                 keygen_jobs, keygen_trials)
    out.append(r)
    r, wc_oracle = whole_trace_vs_oracle(
        "c/wordcount-auto-vs-block1", wordcount_queue(), wc_jobs, wc_trials)
    out.append(r)
    return out, wc_oracle


# -- d: the Pallas kernels, compiled ------------------------------------------

def lowered_has_kernel(fn, *args, **kw):
    return "tpu_custom_call" in fn.lower(*args, **kw).as_text()


def phase_kernels(wc_oracle, wc_jobs=2048, wc_trials=64, T=256, N=4096,
                  nb=64, interpret=False):
    from repro.kernels.maxplus_scan.ops import (_maxplus_entries,
                                                maxplus_entries)
    from repro.kernels.maxplus_scan.ref import maxplus_scan_ref
    from repro.kernels.queue_booking.ops import (_book_stream, book_stream,
                                                 book_stream_ref)
    from repro.sim.vector_queue import wordcount_queue
    out = []
    rng = np.random.default_rng(0)
    for W in (15, 1024):
        ready = jnp.asarray(np.sort(rng.uniform(0, N * 100 / (W * 0.8),
                                                (T, N)), axis=1), jnp.float32)
        service = jnp.asarray(rng.exponential(100.0, (T, N)), jnp.float32)
        wf0 = jnp.asarray(rng.uniform(0, 300.0, (T, W)), jnp.float32)
        t0 = time.perf_counter()
        got = jax.device_get(book_stream(ready, service, wf0, block=64,
                                         interpret=interpret))
        k_s = time.perf_counter() - t0
        want = jax.device_get(book_stream_ref(ready, service, wf0))
        bitwise = all(np.array_equal(a, b) for a, b in zip(got, want))
        kernel = (not interpret) and lowered_has_kernel(
            _book_stream, ready, service, wf0, block=128, interpret=False)
        out.append(report(f"d/book_stream-W{W}", ok=bitwise and (
            interpret or kernel), bitwise=bitwise, T=T, N=N, W=W, block=64,
            interpret=interpret, tpu_custom_call=kernel, first_call_s=k_s))
    W = 15
    diag = jnp.zeros((T, nb, W), jnp.float32)   # the engines' operators
    off = rng.integers(0, 1000, (T, nb, W)).astype(np.float32)
    off = jnp.asarray(np.where(rng.uniform(size=off.shape) < 0.25,
                               -np.inf, off))
    wf0 = jnp.asarray(rng.integers(0, 500, (T, W)), jnp.float32)
    got = jax.device_get(maxplus_entries(diag, off, wf0,
                                         interpret=interpret))
    want = jax.device_get(maxplus_scan_ref(diag, off, wf0))
    bitwise = all(np.array_equal(a, b) for a, b in zip(got, want))
    kernel = (not interpret) and lowered_has_kernel(
        _maxplus_entries, diag, off, wf0, interpret=False)
    out.append(report("d/maxplus_entries-W15", ok=bitwise and (
        interpret or kernel), bitwise=bitwise, T=T, nb=nb, W=W,
        interpret=interpret, tpu_custom_call=kernel))
    # the stock engine, where both kernels sit (the raptor log-depth
    # program alone takes some 300 s to compile on the chip)
    wl = wordcount_queue()
    for label, kw in (("pallas-booking", dict(booking_backend="pallas")),
                      ("logdepth-pallas-summary",
                       dict(scan="logdepth", summary_backend="pallas"))):
        r, _ = whole_trace_vs_oracle(f"d/wordcount-{label}-vs-block1", wl,
                                     wc_jobs, wc_trials, oracle=wc_oracle,
                                     engines=("stock",),
                                     stock_kernel=not interpret, **kw)
        out.append(r)
    return out


# -- e: sharded sweeps on four chips ------------------------------------------

def phase_sharded(devices=4, open_trials=10_000, jobs=4096, trials=64):
    from repro.sim.vector import exponential_vector, sweep_pairs
    from repro.sim.vector_queue import keygen_queue, rate_sweep
    # tests/test_sweeps.py's grid: an AZ axis at fixed flight plus a flight
    # axis (two pow2 buckets), so padding, bucketing and the stock
    # single-bucket path all cross the shard boundary
    grid = ([dict(flight=4, num_azs=a) for a in (1, 2, 3)]
            + [dict(flight=f, num_azs=8) for f in (2, 4)])
    wl = keygen_queue()
    rates = [u * HA["num_workers"] / wl.work_est_ws
             for u in np.linspace(0.1, 0.85, 8)]
    out = []
    for name, sweep in (
            ("e/sweep_pairs", lambda d: sweep_pairs(
                exponential_vector(2, 1000.0), grid, trials=open_trials,
                seed=0, devices=d)),
            ("e/rate_sweep", lambda d: rate_sweep(
                wl, rates, jobs=jobs, trials=trials, seed=0, devices=d))):
        walls = {}
        res = {}
        for d in (1, devices):
            sweep(d)                                  # compile
            t0 = time.perf_counter()
            res[d] = json.dumps(sweep(d), sort_keys=True)
            walls[d] = time.perf_counter() - t0
        bitwise = res[1] == res[devices]
        out.append(report(name, ok=bitwise, bitwise=bitwise,
                          devices=devices, points=len(json.loads(res[1])),
                          wall_s_1=walls[1], wall_s_n=walls[devices]))
    return out


# -- main ---------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded sweep phase, on 4 chips")
    args = ap.parse_args(argv)
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} ({dev.device_kind})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"compile cache {enable_compile_cache()}", flush=True)

    results = []

    def run(name, fn, *a):
        try:
            got = fn(*a)
        except Exception:
            traceback.print_exc()
            report(name, ok=False, error=traceback.format_exc(limit=1))
            results.append(False)
            return None
        rows = got[0] if isinstance(got, tuple) else got
        results.extend(bool(r["ok"]) for r in rows)
        return got

    if args.chips == 4:
        run("e", phase_sharded, 4)
        count = 4
    else:
        run("a", phase_service)
        run("b", phase_fleet)
        exact = run("c", phase_exact)
        if exact is not None:
            run("d", phase_kernels, exact[1])
        else:
            results.append(False)
        count = len(devs)
    if not results or not all(results):
        print(f"FAILED: {results.count(False)} of {len(results)} checks",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
