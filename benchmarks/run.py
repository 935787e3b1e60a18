"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows — us_per_call is the harness
wall time per simulated/served job; derived is the table's headline metric.

    PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}")
    sys.stdout.flush()


def bench_table6_overhead():
    from repro.sim.experiments import table6_overhead
    t0 = time.time()
    rows = table6_overhead(n=20000)
    us = (time.time() - t0) * 1e6 / (6 * 20000)
    med = rows["three_az/medium"]
    _row("table6_overhead", us,
         f"3az_medium_median={med['median']:.1f}ms_p90={med['p90']:.1f}ms"
         f"_paper=9/16ms")


def bench_table7_keygen(dur):
    from repro.sim.experiments import table7_keygen
    t0 = time.time()
    r = table7_keygen(duration_s=dur)
    n = r["stock"]["n"] + r["raptor"]["n"]
    us = (time.time() - t0) * 1e6 / max(n, 1)
    _row("table7_keygen", us,
         f"stock_mean={r['stock']['mean']:.0f}ms"
         f"_raptor_mean={r['raptor']['mean']:.0f}ms"
         f"_ratio={r['mean_ratio']:.3f}_paper=0.647_theory=0.667")


def bench_fig6_scale(dur):
    from repro.sim.experiments import fig6_scale_effect
    t0 = time.time()
    out = fig6_scale_effect(duration_s=dur)
    us = (time.time() - t0) * 1e6 / sum(
        v["stock"]["n"] + v["raptor"]["n"] for v in out.values())
    # the 1-AZ point is compared at low load: at 5 workers a flight of 2
    # doubles per-job worker demand, so "moderate" load queues — the effect
    # the paper notes as Kafka-queue domination at high load (§4.2.1)
    _row("fig6_scale_effect", us,
         f"one_az_low_ratio={out['one_az_5w/low']['mean_ratio']:.3f}"
         f"_one_az_med_ratio={out['one_az_5w/medium']['mean_ratio']:.3f}"
         f"_three_az_ratio={out['three_az_15w/medium']['mean_ratio']:.3f}"
         f"_paper=0.99/na/0.65")


def bench_fig7_workloads(dur):
    from repro.sim.experiments import fig7_other_workloads
    t0 = time.time()
    out = fig7_other_workloads(duration_s=dur)
    n = sum(v["stock"]["n"] + v["raptor"]["n"] for v in out.values())
    us = (time.time() - t0) * 1e6 / max(n, 1)
    _row("fig7_wordcount", us,
         f"ratio={out['wordcount']['mean_ratio']:.3f}_paper=0.455")
    _row("fig7_thumbnail", us,
         f"ratio={out['thumbnail']['mean_ratio']:.3f}_paper=0.892")


def bench_fig8_reliability(dur):
    from repro.sim.experiments import fig8_reliability
    t0 = time.time()
    out = fig8_reliability(n_jobs_s=dur)
    us = (time.time() - t0) * 1e6 / max(len(out), 1)
    r = out["n4/p0.2"]
    _row("fig8_reliability", us,
         f"n4_p0.2_stock={r['stock_fail']:.3f}(theory={r['theory_stock']:.3f})"
         f"_raptor={r['raptor_fail']:.4f}(exact={r['theory_raptor_exact']:.4f})")


def _scalar_jobs_per_s(wl_fn, deployment, load, n_jobs, *, raptor=True,
                       seed=0):
    """Event-driven oracle throughput on one config, sized to ~n_jobs."""
    from repro.sim.cluster import Cluster
    from repro.sim.experiments import rate_for
    from repro.sim.flights import FlightSim
    wl = wl_fn()
    rate = rate_for(wl, deployment, load)
    sim = FlightSim(Cluster(seed=seed, **deployment), wl, raptor=raptor,
                    arrival_rate_hz=rate, duration_s=n_jobs / rate,
                    load=load, seed=seed)
    t0 = time.time()
    jobs = sim.run()
    return len(jobs), time.time() - t0


def bench_sim_vector(trials: int = 10000):
    """Vectorized MC sim vs the scalar event-driven FlightSim, per tier:

    * open_loop — the PR-1 zero-queueing batch (Table-7 keygen config);
    * queue     — the closed-loop M/G/c engine on the SEQUENTIAL ORACLE
                  path (block=1: plain event scan, conservative race
                  budget — bit-for-bit the pre-blocking engine), cold vs
                  warm compile recorded (persistent cache);
    * queue_blocked — the same workload/jobs/trials on the blocked
                  event-replay core (sim/scan_core.py) at its auto
                  config: chunked replay + tight K-completion races,
                  results bitwise equal to the oracle (checked in-bench);
    * queue_logdepth — the same shape through the associative max-plus
                  summary chain (scan="logdepth", adaptive split), bitwise
                  the oracle; honest host number — the mode is work-bound
                  on CPUs (EXPERIMENTS.md §log-depth);
    * dag       — the wordcount DAG manifest through the dependency-masked
                  flight scan, closed loop at medium load (blocked core);
    * queue-stock-taskfcfs — the task-granular stock replay (wordcount
                  STOCK at util 0.75), ≥20x the scalar oracle;
    * queue_streaming — the open-arrival streaming scheduler service
                  (sim/streaming.py): one MMPP stream microbatched onto
                  the persistent device-resident W-state — SUSTAINED
                  jobs/s plus p50/p99 sojourn and SLO-violation fraction
                  under open load, bitwise-checked against the
                  whole-trace block=1 oracle in-bench;
    * sweep-sharded — the closed-loop utilisation grid through the
                  device-sharded SweepPlan driver (sim/sweeps.py), all
                  (forced-host) devices vs one: ≥2x grid throughput on a
                  4-device host, summaries bit-identical.

    Every closed-loop tier records compile_cold_s/compile_warm_s.  The
    metric is jobs/sec at matched job counts; results land in
    BENCH_sim.json so CI can gate on regressions (benchmarks/
    check_regression.py).
    """
    import jax
    import numpy as np
    from repro.sim.experiments import HA
    from repro.sim.faults import FaultProfile
    from repro.sim.policies import RecoveryPolicy
    from repro.sim.vector import VectorFlightSim, keygen_vector
    from repro.sim.vector_queue import (QueueFlightSim, keygen_queue,
                                        load_sweep, wordcount_queue)
    from repro.sim.workloads import keygen_workload, wordcount_workload

    record = {"trials": trials}
    # the PR-4 recording's queue tier (the engine the blocked core
    # replaced), pinned as a constant so the provenance anchor cannot
    # drift when this run overwrites BENCH_sim.json: every regeneration
    # reports the blocked core's speedup against the same seed number
    prior_queue_tps = 378886.96846149676
    # the PR-5 recording's queue_blocked tier — the ISSUE-6 acceptance
    # anchor for the log-depth chain, pinned for the same reason
    prior_blocked_tps = 948490.4927918591

    # ---- open loop (legacy layout: top-level scalar/vector/speedup) ----
    n_jobs, scalar_s = _scalar_jobs_per_s(keygen_workload, HA, "medium",
                                          trials)
    scalar_tps = n_jobs / scalar_s
    vec = VectorFlightSim(keygen_vector(), num_azs=3, flight=2, seed=0)
    t0 = time.time()
    vec.run(trials, raptor=True).response_ms.block_until_ready()
    compile_s = time.time() - t0
    # best-of-reps: the box runs other work, and one stalled rep would
    # otherwise report a phantom regression to the CI gate
    reps = 5

    def best_of(fn):
        best = float("inf")
        for _ in range(reps):
            t0 = time.time()
            fn()
            best = min(best, time.time() - t0)
        return best

    res = vec.run(trials, raptor=True)
    vector_s = best_of(
        lambda: vec.run(trials, raptor=True).response_ms.block_until_ready())
    vector_tps = trials / vector_s
    record["scalar"] = {"jobs": n_jobs, "wall_s": scalar_s,
                        "trials_per_s": scalar_tps}
    record["vector"] = {"wall_s": vector_s, "compile_s": compile_s,
                        "trials_per_s": vector_tps,
                        "mean_ms": res.summary()["mean"]}
    record["speedup"] = vector_tps / scalar_tps
    _row("sim_vector", vector_s * 1e6 / trials,
         f"scalar={scalar_tps:.0f}t/s_vector={vector_tps:.0f}t/s"
         f"_speedup={record['speedup']:.0f}x_target>=50x")

    def cold_warm(run):
        """Cold compile, then warm (in-memory exes dropped, persistent
        disk cache hot) — recorded for every closed-loop tier."""
        t0 = time.time()
        out = run()
        out.response_ms.block_until_ready()
        cold = time.time() - t0
        jax.clear_caches()        # drop in-memory exe; reload from disk
        t0 = time.time()
        run().response_ms.block_until_ready()
        return out, cold, time.time() - t0

    # ---- closed-loop queue: the sequential ORACLE path (block=1) -------
    # block=1 pins the plain event scan with the conservative full race
    # budget — bit-for-bit the pre-blocking engine, the configuration the
    # blocked core is verified against (tests/test_queue_properties.py)
    q_jobs = max(trials // 8, 256)
    q_trials = 48
    qsim = QueueFlightSim(keygen_queue(), load="medium", seed=0, block=1,
                          **HA)
    r, cold_s, warm_s = cold_warm(
        lambda: qsim.run(q_jobs, q_trials, raptor=True))
    q_wall = best_of(
        lambda: qsim.run(q_jobs, q_trials,
                         raptor=True).response_ms.block_until_ready())
    q_tps = q_jobs * q_trials / q_wall
    sn, ss = _scalar_jobs_per_s(keygen_workload, HA, "medium",
                                min(q_jobs * q_trials, 8192))
    record["queue"] = {
        "vector_jobs": q_jobs * q_trials, "wall_s": q_wall,
        "jobs_per_s": q_tps, "compile_cold_s": cold_s,
        "compile_warm_s": warm_s,
        "scalar_jobs_per_s": sn / ss, "speedup": q_tps / (sn / ss),
        "mean_ms": r.summary()["mean"],
    }
    _row("sim_queue", q_wall * 1e6 / (q_jobs * q_trials),
         f"scalar={sn/ss:.0f}j/s_vector={q_tps:.0f}j/s"
         f"_speedup={q_tps/(sn/ss):.0f}x_cold={cold_s:.1f}s"
         f"_warm={warm_s:.2f}s_target>=50x")

    # ---- queue_blocked: the blocked event-replay core, same shape ------
    # same workload at EQUAL jobs/trials on the blocked substrate's auto
    # config (chunked replay + tight K-completion race budget); responses
    # must be bitwise the oracle's, and the acceptance anchor is the
    # speedup over the seed recording's queue tier (>= 2x)
    bsim = QueueFlightSim(keygen_queue(), load="medium", seed=0, **HA)
    rb, b_cold, b_warm = cold_warm(
        lambda: bsim.run(q_jobs, q_trials, raptor=True))
    b_wall = best_of(
        lambda: bsim.run(q_jobs, q_trials,
                         raptor=True).response_ms.block_until_ready())
    b_tps = q_jobs * q_trials / b_wall
    blk, res_mode, _ = bsim.engine_config("raptor")
    exact = bool(np.array_equal(np.asarray(rb.response_ms),
                                np.asarray(r.response_ms)))
    record["queue_blocked"] = {
        "vector_jobs": q_jobs * q_trials, "wall_s": b_wall,
        "jobs_per_s": b_tps, "compile_cold_s": b_cold,
        "compile_warm_s": b_warm, "block": blk, "resolver": res_mode,
        "bitwise_equals_oracle": exact,
        "vs_queue_oracle": b_tps / q_tps,
        "baseline_queue_jobs_per_s": prior_queue_tps,
        "speedup_vs_baseline_queue": (
            b_tps / prior_queue_tps if prior_queue_tps else None),
        "mean_ms": rb.summary()["mean"],
    }
    base_txt = (f"_vs_seed={b_tps / prior_queue_tps:.2f}x"
                if prior_queue_tps else "")
    _row("sim_queue_blocked", b_wall * 1e6 / (q_jobs * q_trials),
         f"oracle={q_tps:.0f}j/s_blocked={b_tps:.0f}j/s"
         f"_x{b_tps/q_tps:.2f}{base_txt}_block={blk}/{res_mode}"
         f"_bitwise={exact}_cold={b_cold:.1f}s_warm={b_warm:.2f}s"
         f"_target>=2x_vs_seed")

    # ---- queue_logdepth: the associative max-plus summary chain --------
    # same workload at EQUAL jobs/trials with scan="logdepth" (block 0 =
    # the adaptive ceil(n/3) split); responses must stay bitwise the
    # oracle's.  The ISSUE-6 acceptance target was the PR-5 queue_blocked
    # recording, but the mode is work-bound on hosts: the block-level
    # Jacobi gains exactly ONE exact block per outer pass in every load
    # regime (worker choice is bitwise-coupled to the entry vector), so
    # nb blocks cost nb x the bookings and the host optimum (nb=2 + tail)
    # still pays ~1.7x the sequential chain's work.  The honest number is
    # recorded as-is; the mode's value is depth, not host throughput
    # (EXPERIMENTS.md §log-depth).
    lsim = QueueFlightSim(keygen_queue(), load="medium", seed=0,
                          scan="logdepth", **HA)
    rl, l_cold, l_warm = cold_warm(
        lambda: lsim.run(q_jobs, q_trials, raptor=True))
    l_wall = best_of(
        lambda: lsim.run(q_jobs, q_trials,
                         raptor=True).response_ms.block_until_ready())
    l_tps = q_jobs * q_trials / l_wall
    l_blk, l_res, l_scan = lsim.engine_config("raptor")
    l_exact = bool(np.array_equal(np.asarray(rl.response_ms),
                                  np.asarray(r.response_ms)))
    record["queue_logdepth"] = {
        "vector_jobs": q_jobs * q_trials, "wall_s": l_wall,
        "jobs_per_s": l_tps, "compile_cold_s": l_cold,
        "compile_warm_s": l_warm, "block": l_blk, "resolver": l_res,
        "scan": l_scan, "bitwise_equals_oracle": l_exact,
        "vs_queue_blocked": l_tps / b_tps,
        "baseline_blocked_jobs_per_s": prior_blocked_tps,
        "beats_baseline_blocked": bool(l_tps > prior_blocked_tps),
        "mean_ms": rl.summary()["mean"],
    }
    _row("sim_queue_logdepth", l_wall * 1e6 / (q_jobs * q_trials),
         f"blocked={b_tps:.0f}j/s_logdepth={l_tps:.0f}j/s"
         f"_x{l_tps/b_tps:.2f}_block={l_blk}/{l_res}"
         f"_bitwise={l_exact}_cold={l_cold:.1f}s_warm={l_warm:.2f}s"
         f"_host_workbound")

    # ---- DAG workload (wordcount) through the dep-masked scan ----------
    d_jobs, d_trials = max(trials // 16, 128), 16
    dsim = QueueFlightSim(wordcount_queue(), load="medium", seed=0, **HA)
    r, d_cold, d_warm = cold_warm(
        lambda: dsim.run(d_jobs, d_trials, raptor=True))
    d_wall = best_of(
        lambda: dsim.run(d_jobs, d_trials,
                         raptor=True).response_ms.block_until_ready())
    d_tps = d_jobs * d_trials / d_wall
    sn, ss = _scalar_jobs_per_s(wordcount_workload, HA, "medium",
                                min(d_jobs * d_trials, 4096))
    record["dag_wordcount"] = {
        "vector_jobs": d_jobs * d_trials, "jobs_per_s": d_tps,
        "compile_cold_s": d_cold, "compile_warm_s": d_warm,
        "scalar_jobs_per_s": sn / ss, "speedup": d_tps / (sn / ss),
        "mean_ms": r.summary()["mean"],
    }
    _row("sim_dag", d_wall * 1e6 / (d_jobs * d_trials),
         f"scalar={sn/ss:.0f}j/s_vector={d_tps:.0f}j/s"
         f"_speedup={d_tps/(sn/ss):.0f}x_cold={d_cold:.1f}s"
         f"_warm={d_warm:.2f}s")

    # ---- dag_manifest: a compiled workload-bank graph, conditionals on -
    # The ETL pipeline straight from the workflow-manifest compiler
    # (core/workflow.py): wide transform fan-out behind a data-dependent
    # validate conditional (poison jobs detour to quarantine via the
    # mask-select path).  Tracks the compiler->engine route's throughput
    # at the auto blocked config, and pins the conditional scan's blocked
    # replay bitwise against the block=1 oracle in-bench — runs AND ok
    # bits (failure routing is the point of the graph).
    from repro.sim.vector_queue import etl_queue
    m_jobs, m_trials = max(trials // 32, 64), 8
    m_wl = etl_queue()
    msim = QueueFlightSim(m_wl, load="medium", seed=0, **HA)
    rm, m_cold, m_warm = cold_warm(
        lambda: msim.run(m_jobs, m_trials, raptor=True))
    m_wall = best_of(
        lambda: msim.run(m_jobs, m_trials,
                         raptor=True).response_ms.block_until_ready())
    m_tps = m_jobs * m_trials / m_wall
    m1sim = QueueFlightSim(m_wl, load="medium", seed=0, block=1, **HA)
    rm1 = m1sim.run(m_jobs, m_trials, raptor=True)
    m_exact = bool(
        np.array_equal(np.asarray(rm.response_ms),
                       np.asarray(rm1.response_ms))
        and np.array_equal(np.asarray(rm.ok), np.asarray(rm1.ok)))
    m_blk, m_res, _ = msim.engine_config("raptor")
    record["dag_manifest"] = {
        "graph": m_wl.graph.name, "manifest_hash": m_wl.graph.manifest_hash,
        "tasks": m_wl.graph.K, "vector_jobs": m_jobs * m_trials,
        "wall_s": m_wall, "jobs_per_s": m_tps,
        "compile_cold_s": m_cold, "compile_warm_s": m_warm,
        "block": m_blk, "resolver": m_res,
        "bitwise_equals_oracle": m_exact,
        "mean_ms": rm.summary()["mean"],
        "fail_rate": rm.summary()["fail_rate"],
    }
    _row("sim_dag_manifest", m_wall * 1e6 / (m_jobs * m_trials),
         f"etl={m_tps:.0f}j/s_block={m_blk}/{m_res}_bitwise={m_exact}"
         f"_cold={m_cold:.1f}s_warm={m_warm:.2f}s"
         f"_hash={m_wl.graph.manifest_hash}")

    # ---- queue-stock-taskfcfs: the task-granular stock engine ----------
    # wordcount STOCK at util 0.75 (load="high") — the regime the
    # task-FCFS rewrite made faithful (tests/test_sim_queue.py pins the
    # <10% mean/p99 agreement).  Benched at stock_extra_passes=0, the
    # minimal scan-over-stage-depth configuration (also fidelity-tested);
    # 256 jobs/trial keeps the queue in regime (~95s windows) while the
    # sequential event scan stays short, and the trial axis carries the
    # parallelism.
    tf_jobs, tf_trials = 256, max(trials // 80, 24)
    tfsim = QueueFlightSim(wordcount_queue(), load="high", seed=0,
                           stock_extra_passes=0, **HA)
    r, tf_cold, tf_warm = cold_warm(
        lambda: tfsim.run(tf_jobs, tf_trials, raptor=False))
    tf_wall = best_of(
        lambda: tfsim.run(tf_jobs, tf_trials,
                          raptor=False).response_ms.block_until_ready())
    tf_tps = tf_jobs * tf_trials / tf_wall
    sn, ss = _scalar_jobs_per_s(wordcount_workload, HA, "high",
                                min(tf_jobs * tf_trials, 4096),
                                raptor=False)
    record["queue_stock_taskfcfs"] = {
        "vector_jobs": tf_jobs * tf_trials, "jobs_per_s": tf_tps,
        "compile_cold_s": tf_cold, "compile_warm_s": tf_warm,
        "scalar_jobs_per_s": sn / ss, "speedup": tf_tps / (sn / ss),
        "mean_ms": r.summary()["mean"],
    }
    _row("sim_stock_taskfcfs", tf_wall * 1e6 / (tf_jobs * tf_trials),
         f"scalar={sn/ss:.0f}j/s_vector={tf_tps:.0f}j/s"
         f"_speedup={tf_tps/(sn/ss):.0f}x_cold={tf_cold:.1f}s"
         f"_warm={tf_warm:.2f}s_target>=20x")

    # ---- queue_faults: the attempt-expanded fault/policy path ----------
    # keygen under Markov-modulated AZ brownouts + worker crashes with a
    # timeout/retry/hedge recovery policy (sim/faults.py, sim/policies.py).
    # The attempt expansion multiplies the event stream by (1 + retries +
    # hedge), so this tier tracks the fault path's own throughput AND pins
    # its blocked-replay bitwise invariance against the block=1 oracle —
    # the same acceptance the fault property tests enforce.
    f_prof = FaultProfile(az_mtbf_ms=24_000.0, az_mttr_ms=6_000.0,
                          degraded_inflation=2.0, degraded_fail_prob=0.05,
                          crash_mtbf_ms=400_000.0, crash_restart_ms=2_000.0)
    f_pol = RecoveryPolicy(timeout_ms=6_000.0, max_retries=1,
                           backoff_ms=50.0, hedge_ms=2_500.0)
    f_jobs, f_trials = max(trials // 16, 128), 16
    fwl = keygen_queue(fail_prob=0.01, faults=f_prof, recovery=f_pol)
    fsim = QueueFlightSim(fwl, load="medium", seed=0, **HA)
    rf, f_cold, f_warm = cold_warm(
        lambda: fsim.run(f_jobs, f_trials, raptor=True))
    f_wall = best_of(
        lambda: fsim.run(f_jobs, f_trials,
                         raptor=True).response_ms.block_until_ready())
    f_tps = f_jobs * f_trials / f_wall
    f1sim = QueueFlightSim(fwl, load="medium", seed=0, block=1, **HA)
    rf1 = f1sim.run(f_jobs, f_trials, raptor=True)
    f_exact = bool(np.array_equal(np.asarray(rf.response_ms),
                                  np.asarray(rf1.response_ms)))
    f_blk, f_res, _ = fsim.engine_config("raptor")
    record["queue_faults"] = {
        "vector_jobs": f_jobs * f_trials, "wall_s": f_wall,
        "jobs_per_s": f_tps, "compile_cold_s": f_cold,
        "compile_warm_s": f_warm, "block": f_blk, "resolver": f_res,
        "bitwise_equals_oracle": f_exact,
        "vs_queue_nofault": f_tps / b_tps,
        "mean_ms": rf.summary()["mean"],
        "fail_rate": rf.summary()["fail_rate"],
    }
    _row("sim_queue_faults", f_wall * 1e6 / (f_jobs * f_trials),
         f"faulty={f_tps:.0f}j/s_x{f_tps/b_tps:.2f}_vs_nofault"
         f"_block={f_blk}/{f_res}_bitwise={f_exact}"
         f"_cold={f_cold:.1f}s_warm={f_warm:.2f}s")

    # ---- queue_streaming: open MMPP arrivals, persistent W-state -------
    # The streaming scheduler service (sim/streaming.py): ONE open
    # arrival stream microbatched onto the persistent device-resident
    # free-at vector, host ingest pipelined against device booking.
    # Unlike the batch tiers there is no trial axis to vmap — jobs/s here
    # is SUSTAINED single-stream service throughput under bursty (MMPP)
    # open load, with the latency distribution (p50/p99 sojourn, SLO
    # violations) the service exists to measure.  Bitwise acceptance
    # rides along: the booked stream replayed whole-trace through the
    # block=1 oracle must match exactly (oracle_check).
    from repro.sim.events import MMPPArrivals
    from repro.sim.streaming import oracle_check, run_open_load
    s_sim = QueueFlightSim(keygen_queue(), load="medium", seed=0, **HA)
    st_jobs = max(trials // 2, 1024)
    st_mb = 128

    def st_mmpp():
        return MMPPArrivals(s_sim.rate_hz, burst_factor=5.0,
                            dwell_s=(20.0, 4.0), seed=1)

    t0 = time.time()
    run_open_load(s_sim, jobs=st_mb, microbatch=st_mb, process=st_mmpp(),
                  warmup=False, seed=0)
    st_cold = time.time() - t0
    jax.clear_caches()            # drop in-memory exe; reload from disk
    t0 = time.time()
    run_open_load(s_sim, jobs=st_mb, microbatch=st_mb, process=st_mmpp(),
                  warmup=False, seed=0)
    st_warm = time.time() - t0
    st_rep = None
    for _ in range(reps):
        r = run_open_load(s_sim, jobs=st_jobs, microbatch=st_mb,
                          process=st_mmpp(), warmup=False, seed=0)
        if st_rep is None or r.jobs_per_s > st_rep.jobs_per_s:
            st_rep = r
    st_exact = oracle_check(s_sim, n_steps=4, microbatch=32)["bitwise"]
    st_blk, st_res, _ = s_sim.engine_config("raptor")
    record["queue_streaming"] = {
        "jobs": st_rep.jobs, "microbatch": st_mb,
        "jobs_per_s": st_rep.jobs_per_s, "wall_s": st_rep.wall_s,
        "compile_cold_s": st_cold, "compile_warm_s": st_warm,
        "block": st_blk, "resolver": st_res,
        "arrivals": "mmpp", "offered_rate_hz": st_rep.offered_rate_hz,
        "mean_ms": st_rep.mean_ms, "p50_ms": st_rep.p50_ms,
        "p99_ms": st_rep.p99_ms, "slo_ms": st_rep.slo_ms,
        "slo_violation_frac": st_rep.slo_violation_frac,
        "bitwise_equals_oracle": st_exact,
    }
    _row("sim_queue_streaming", st_rep.wall_s * 1e6 / st_rep.jobs,
         f"sustained={st_rep.jobs_per_s:.0f}j/s_p99={st_rep.p99_ms:.0f}ms"
         f"_slo_viol={st_rep.slo_violation_frac:.3f}"
         f"_block={st_blk}/{st_res}_bitwise={st_exact}"
         f"_cold={st_cold:.1f}s_warm={st_warm:.2f}s")

    # ---- sweep-sharded: the config grid over the device mesh -----------
    # The closed-loop utilisation grid through the SweepPlan driver
    # (sim/sweeps.py), config axis sharded over every (forced-host)
    # device vs pinned to one.  The closed-loop event scans are tiny-op
    # dispatch-bound work XLA cannot intra-op-parallelize, so this is
    # where device sharding pays near-linearly; the open-loop cores
    # already saturate the host on one device, so the sweep_scale grid
    # is checked for sharded == single-device summaries instead (the
    # shard axis is pure batching — results must be bit-identical).
    from repro.sim.vector_queue import rate_sweep
    n_dev = jax.device_count()
    wl_q = keygen_queue()
    utils = [0.1 + 0.75 * i / 11 for i in range(12)]
    rates = [u * HA["num_workers"] / wl_q.work_est_ws for u in utils]
    sh_jobs, sh_trials = max(trials // 16, 256), 16

    def sweep_grid(devices):
        return rate_sweep(wl_q, rates, num_workers=HA["num_workers"],
                          num_azs=HA["num_azs"], jobs=sh_jobs,
                          trials=sh_trials, seed=0, devices=devices)

    one = sweep_grid(1)               # compile outside the timed window
    sharded = sweep_grid(None)
    one_wall = best_of(lambda: sweep_grid(1))
    sh_wall = best_of(lambda: sweep_grid(None))
    grid_jobs = len(rates) * sh_jobs * sh_trials * 2
    from repro.sim.vector import exponential_vector, sweep_pairs
    scale_grid = ([dict(flight=4, num_azs=a) for a in (1, 2, 3, 4, 6, 8)]
                  + [dict(flight=f, num_azs=8) for f in (2, 4, 8, 16)])
    wl_o = exponential_vector(2, 1000.0)
    sc_trials = min(trials, 4000)
    scale_match = (
        sweep_pairs(wl_o, scale_grid, trials=sc_trials, seed=0, devices=1)
        == sweep_pairs(wl_o, scale_grid, trials=sc_trials, seed=0,
                       devices=None))
    record["sweep_sharded"] = {
        "devices": n_dev, "grid_points": len(rates),
        "vector_jobs": grid_jobs,
        "jobs_per_s": grid_jobs / sh_wall,
        "jobs_per_s_1dev": grid_jobs / one_wall,
        "multiplier": one_wall / sh_wall,
        "summaries_match": bool(one == sharded),
        "scale_grid_summaries_match": bool(scale_match),
    }
    _row("sim_sweep_sharded", sh_wall * 1e6 / grid_jobs,
         f"1dev={grid_jobs/one_wall:.0f}j/s_sharded={grid_jobs/sh_wall:.0f}j/s"
         f"_x{one_wall/sh_wall:.2f}_devices={n_dev}"
         f"_match={bool(one == sharded)}_scale_match={bool(scale_match)}"
         f"_target>=2x_on_4dev")

    # ---- the fig6-equivalent load sweep (acceptance: >=50x) ------------
    s_jobs = 0
    s_wall = 0.0
    from repro.sim.experiments import LOW_AVAIL
    for dep in (LOW_AVAIL, HA):
        for load in ("low", "medium", "high"):
            for raptor in (False, True):
                n, s = _scalar_jobs_per_s(
                    keygen_workload, dep, load, max(trials // 8, 256),
                    raptor=raptor)
                s_jobs += n
                s_wall += s
    sw_jobs, sw_trials = max(trials // 4, 512), 48

    def fig6_vector():
        for dep in (LOW_AVAIL, HA):
            load_sweep(keygen_queue(), num_workers=dep["num_workers"],
                       num_azs=dep["num_azs"], jobs=sw_jobs,
                       trials=sw_trials, seed=0)

    fig6_vector()                 # compile outside the timed window
    v_wall = best_of(fig6_vector)
    v_jobs = sw_jobs * sw_trials * 3 * 2 * 2
    record["fig6_sweep"] = {
        "scalar_jobs": s_jobs, "scalar_jobs_per_s": s_jobs / s_wall,
        "vector_jobs": v_jobs, "vector_jobs_per_s": v_jobs / v_wall,
        "speedup": (v_jobs / v_wall) / (s_jobs / s_wall),
    }
    _row("sim_fig6_sweep", v_wall * 1e6 / v_jobs,
         f"scalar={s_jobs/s_wall:.0f}j/s_vector={v_jobs/v_wall:.0f}j/s"
         f"_speedup={record['fig6_sweep']['speedup']:.0f}x_target>=50x")

    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_sim.json")
    with open(os.path.abspath(path), "w") as f:
        json.dump(record, f, indent=2)


def bench_engine_speculation():
    """Live threaded engine: speculative flight on real jitted stages."""
    import jax
    import numpy as np
    from repro.configs import get_config, reduced_config
    from repro.models import init_params
    from repro.serving.engine import ServeConfig, ServingEngine, demo_requests

    cfg = reduced_config(get_config("gemma-2b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, ServeConfig(
        max_len=24, decode_steps=4, flight_size=2, mean_jitter_s=0.05))
    batch = demo_requests(cfg, batch=2, prompt_len=8)
    eng.generate(batch)                       # warm up jits
    stock, raptor = [], []
    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(8):
        r1 = eng.generate(batch)
        stock.append(r1.latency_s + rng.exponential(0.05, 2).sum())
        r2 = eng.generate_flight(batch)
        raptor.append(r2.latency_s)
    us = (time.time() - t0) * 1e6 / 16
    _row("engine_speculation", us,
         f"stock_mean={np.mean(stock)*1e3:.0f}ms"
         f"_flight_mean={np.mean(raptor)*1e3:.0f}ms_exact_tokens=True")


def bench_kernels():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention.kernel import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 256, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 256, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 256, 64))
    t0 = time.time()
    out = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    us = (time.time() - t0) * 1e6
    err = float(jnp.max(jnp.abs(out - attention_ref(q, k, v))))
    _row("kernel_flash_interpret", us, f"max_err={err:.2e}")


def bench_roofline():
    path = os.path.join(os.path.dirname(__file__), "..", "dryrun_results.json")
    path = os.path.abspath(path)
    if not os.path.exists(path):
        _row("roofline", 0.0, "dryrun_results.json_missing_run_dryrun_first")
        return
    sys.path.insert(0, os.path.dirname(__file__))
    from roofline import table
    rows = table(path)
    for r in rows:
        _row(f"roofline/{r['arch']}/{r['shape']}", 0.0,
             f"compute={r['t_compute_s']:.4f}s_memory={r['t_memory_s']:.4f}s"
             f"_coll={r['t_collective_s']:.4f}s_dom={r['dominant']}"
             f"_useful={r['useful_ratio']:.2f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("targets", nargs="*",
                    help="subset of benches to run (e.g. sim-vector); "
                         "empty = the full paper sweep")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--skip-engine", action="store_true")
    ap.add_argument("--trials", type=int, default=10000,
                    help="vector-sim trial count for sim-vector")
    args, _ = ap.parse_known_args()
    dur = 200.0 if args.fast else 600.0
    print("name,us_per_call,derived")
    # single registry: insertion order is the full-sweep order; targets in
    # JAX_TIER need jax and are dropped by --skip-engine so the scalar
    # numpy-only sweep keeps working on a bare interpreter
    named = {
        "table6": bench_table6_overhead,
        "table7": lambda: bench_table7_keygen(dur),
        "fig6": lambda: bench_fig6_scale(dur),
        "fig7": lambda: bench_fig7_workloads(dur),
        "fig8": lambda: bench_fig8_reliability(min(dur, 400.0)),
        "sim-vector": lambda: bench_sim_vector(args.trials),
        "engine": bench_engine_speculation,
        "kernels": bench_kernels,
        "roofline": bench_roofline,
    }
    jax_tier = {"sim-vector", "engine", "kernels"}
    targets = args.targets or [t for t in named
                               if not (args.skip_engine and t in jax_tier)]
    # fig6/fig7 run the vector engine, so they share the cache too
    if any(t in jax_tier or t in ("fig6", "fig7") for t in targets):
        from repro.launch.compile_cache import enable_compile_cache
        from repro.sim.sweeps import force_host_devices
        # multi-controller sweeps on a CPU host: split it into 4 devices
        # BEFORE the backend initializes (no-op on the chip, or when
        # XLA_FLAGS already forces a count, e.g. in CI)
        force_host_devices(4)
        enable_compile_cache()
    for t in targets:
        if t not in named:
            raise SystemExit(f"unknown bench target {t!r}; "
                             f"choose from {sorted(named)}")
        named[t]()


if __name__ == "__main__":
    main()
