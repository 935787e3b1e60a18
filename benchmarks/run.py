"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows — us_per_call is the harness
wall time per simulated/served job; derived is the table's headline metric.

    PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import argparse
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


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("targets", nargs="*",
                    help="subset of benches to run (e.g. table7); "
                         "empty = the full paper sweep")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--skip-engine", action="store_true")
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
        "engine": bench_engine_speculation,
        "kernels": bench_kernels,
    }
    jax_tier = {"engine", "kernels"}
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
