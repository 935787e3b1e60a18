"""Device-sharded sweep subsystem: every config-grid sweep through ONE driver.

The paper's claims are sweep-shaped — delay ratio and failure rate vs load,
AZ count, flight size — and before this module each sweep family carried its
own copy of the pad-mask-trace plumbing (``sim/vector.py``'s bucket loop,
``sim/vector_queue.py``'s ``_pair_sweep``, the driver loops in
``sim/experiments.py``) and ran on ONE device.  A :class:`SweepPlan` is the
declarative form of a sweep: a config grid, a set of static-shape *buckets*
(grouped via the shared ``pow2_pad``/``bucket_by_pad`` helpers so ragged
axes like flight size share compilations), and one per-config core per
bucket.  The driver pads each bucket's config axis up to the device mesh,
runs it through ``shard_map`` over the 1-D ``("config",)`` mesh
(``launch.mesh.make_config_mesh``) — pure batching, so the sharded run is
bit-identical to the single-device one (tests/test_sweeps.py) — donates the
stacked per-config input buffers on accelerator backends, and shares the
jitted-runner cache across plans (plus the persistent XLA compile cache,
``repro.launch.compile_cache.enable_compile_cache``, for the cross-process
case).

Multi-controller on CPU hosts: :func:`force_host_devices` forces
``--xla_force_host_platform_device_count`` before the jax backend
initializes, splitting the host into N devices so the sharded path runs —
and is CI-tested — on a plain GitHub runner.  The closed-loop grids shard
near-linearly on a host: their event scans are
tiny-op dispatch-bound work XLA cannot intra-op-parallelize, exactly the
coordinator fan-out Wukong/Archipelago get their wins from.  The open-loop
cores are wide elementwise batches that already saturate a host's cores on
one device, so sharding them buys equivalence coverage, not throughput.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.launch.mesh import make_config_mesh
from repro.sim.cluster import OverheadModel, lognormal_params
from repro.sim.vector import (VectorResult, VectorWorkload, _raptor_sweep_core,
                              _stock_sweep_core, bucket_by_pad)


# --------------------------------------------------------------------------
# CPU fallback: force a host-device mesh before the backend initializes
# --------------------------------------------------------------------------

def force_host_devices(n: int) -> int:
    """Ensure the process sees >= ``n`` devices by forcing XLA's host-
    platform device count — the CPU fallback for the multi-controller sweep
    path, so sharded sweeps run (and are CI-tested) on a GitHub runner.

    Must run before the jax backend initializes (i.e. before the first
    ``jax.devices()`` / jit dispatch).  The flag is APPENDED to any
    user-supplied ``XLA_FLAGS`` (never overwrites it), and a user-set
    device-count flag is respected as-is.  If the backend is already live
    and sees fewer than ``n`` devices, the request cannot take effect —
    that raises a clear ``RuntimeError`` instead of silently running the
    sweep unsharded.  The flag splits only the host CPU: on an
    accelerator backend the devices are the chips, whatever ``n`` says.
    Returns the live device count, so callers size their shard axis on
    the actual value, never the requested one.
    """
    from jax._src import xla_bridge
    flag = "--xla_force_host_platform_device_count"
    user_set = flag in os.environ.get("XLA_FLAGS", "")
    if not user_set and not xla_bridge.backends_are_initialized():
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + f" {flag}={int(n)}").strip()
    if jax.default_backend() != "cpu":
        return jax.device_count()
    if not user_set and jax.device_count() < int(n):
        raise RuntimeError(
            f"force_host_devices({n}) called after the jax backend "
            f"initialized with {jax.device_count()} device(s); call it "
            f"before the first jax.devices()/jit dispatch, or set "
            f"XLA_FLAGS={flag}={int(n)} in the environment")
    return jax.device_count()


def _resolve_devices(devices) -> tuple:
    """None -> every device; int -> first n devices; else as given."""
    if devices is None:
        return tuple(jax.devices())
    if isinstance(devices, int):
        return tuple(jax.devices()[:max(int(devices), 1)])
    return tuple(devices)


# --------------------------------------------------------------------------
# the driver
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SweepTask:
    """One static-shape bucket of a plan.

    ``core(key, cfg, shared)`` computes a single config: ``cfg`` is a tuple
    of that config's knobs, ``shared`` the broadcast arguments.  The driver
    vmaps it over the stacked config axis and shards that axis over the
    device mesh; ``key`` and ``shared`` are replicated to every shard.
    """
    tag: str                      # output slot ("raptor" / "stock")
    idxs: Tuple[int, ...]         # plan-level config indices in this bucket
    core: Callable
    key: object                   # PRNG key array, replicated
    cfg: tuple                    # per-config arrays, leading axis len(idxs)
    shared: tuple                 # broadcast scalars/arrays


@functools.lru_cache(maxsize=None)
def _sharded_runner(core, devices):
    """Jitted (config-vmapped, device-sharded) form of a bucket core.

    Cached per (core, device set); the core builders below are themselves
    lru-cached on their static shapes, so re-running a plan — or another
    plan sharing a bucket shape — reuses the compiled executable.
    """
    fn = jax.vmap(core, in_axes=(None, 0, None))
    if len(devices) > 1:
        P = jax.sharding.PartitionSpec
        # check_vma=False: the per-config event scans carry values that
        # become device-varying inside the scan (their inputs are sharded
        # config rows), which the varying-axes check rejects as a carry
        # type change; the body is pure batching, so nothing is lost
        fn = jax.shard_map(fn, mesh=make_config_mesh(devices),
                           in_specs=(P(), P("config"), P()),
                           out_specs=P("config"), check_vma=False)
    # donating the stacked config buffers is free on accelerators — run()
    # passes per-dispatch copies, never the plan's own arrays, exactly so
    # they are safe to donate; the CPU runtime ignores donation with a
    # warning, so gate it there
    donate = (1,) if jax.default_backend() != "cpu" else ()
    return jax.jit(fn, donate_argnums=donate)


class SweepPlan:
    """A config grid plus the bucketed, device-shardable runners for it.

    ``run(devices=...)`` executes every bucket (config axis padded up to a
    multiple of the shard count with replicas of the bucket's first config,
    sliced back off afterwards) and hands each config's per-tag outputs to
    ``finalize(config, parts) -> dict``.  Because the shard axis is pure
    batching, results are bit-identical for any device count — a sharded
    sweep IS the single-device sweep, just faster.
    """

    def __init__(self, name: str, configs, tasks, finalize):
        self.name = name
        self.configs = list(configs)
        self.tasks = list(tasks)
        self.finalize = finalize
        self.validate()

    def validate(self) -> None:
        """Bucketing must partition the grid per output tag: every config
        index in exactly one bucket — a plan can never silently drop (or
        double-run) grid points."""
        for tag in {t.tag for t in self.tasks}:
            seen = sorted(i for t in self.tasks if t.tag == tag
                          for i in t.idxs)
            if seen != list(range(len(self.configs))):
                raise ValueError(
                    f"plan {self.name!r}: tag {tag!r} buckets cover "
                    f"{len(set(seen))}/{len(self.configs)} grid points")

    def run(self, devices=None) -> List[dict]:
        devs = _resolve_devices(devices)
        parts: List[Dict[str, object]] = [{} for _ in self.configs]
        for task in self.tasks:
            n = len(task.idxs)
            # Never shard down to a local batch of ONE config (except
            # n == 1, where every mesh size degenerates to the same
            # single-config program): a size-1 config axis lets XLA
            # collapse the vmap dimension and re-fuse the local program,
            # which moves transcendentals by an ulp and breaks the
            # bit-identical guarantee.  A local batch >= 2 keeps the
            # traced rank — and with it the per-element codegen — stable
            # across mesh sizes (tests/test_sweeps.py pins this).
            d = 1 if n == 1 else max(1, min(len(devs), n // 2))
            npad = -(-n // d) * d
            # on donating backends the dispatch consumes its input buffers,
            # so hand it COPIES — jnp.asarray would alias the plan's own
            # task.cfg arrays and a second run() would hit deleted buffers
            make = (jnp.array if jax.default_backend() != "cpu"
                    else jnp.asarray)
            cfg = tuple(make(a) for a in task.cfg)
            if npad > n:
                # pad the grid axis with replicas of the bucket's first
                # config; the surplus rows are sliced back off below
                cfg = jax.tree_util.tree_map(
                    lambda a: jnp.concatenate(
                        [a, jnp.broadcast_to(a[:1],
                                             (npad - n,) + a.shape[1:])]),
                    cfg)
            out = _sharded_runner(task.core, devs[:d])(
                task.key, cfg, task.shared)
            # ONE host transfer per output leaf: slicing per-config on
            # device and pulling 0-d results would serialize hundreds of
            # tiny blocking syncs into the timed path
            out = jax.device_get(out)
            for j, i in enumerate(task.idxs):
                parts[i][task.tag] = jax.tree_util.tree_map(
                    lambda o: o[j], out)
        return [self.finalize(c, p) for c, p in zip(self.configs, parts)]


# --------------------------------------------------------------------------
# open-loop pairs (the sim/vector.py family): pad-and-mask over flight size
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _open_raptor_core(trials, f_pad, num_tasks, a_pad, dist, fail_prob,
                      faults, policy):
    def core(key, cfg, shared):
        flight, num_azs, rho, oh_mu, oh_sigma = cfg
        mean, offset, cv, stage_oh, slat = shared
        return _raptor_sweep_core(
            key, flight, num_azs, rho, mean, offset, cv, stage_oh, slat,
            oh_mu, oh_sigma, trials=trials, flight_max=f_pad,
            num_tasks=num_tasks, azs_max=a_pad, dist=dist,
            fail_prob=fail_prob, faults=faults, policy=policy)
    return core


@functools.lru_cache(maxsize=None)
def _open_stock_core(trials, num_tasks, dist, fail_prob, faults, policy):
    def core(key, cfg, shared):
        rho, oh_mu, oh_sigma = cfg
        mean, offset, cv = shared
        return _stock_sweep_core(
            key, rho, mean, offset, cv, oh_mu, oh_sigma, trials=trials,
            num_tasks=num_tasks, dist=dist, fail_prob=fail_prob,
            faults=faults, policy=policy)
    return core


def open_loop_pair_plan(wl: VectorWorkload, configs, *, trials: int = 20_000,
                        seed: int = 0) -> SweepPlan:
    """``sweep_pairs`` as a plan: many (flight, num_azs, rho, load) points,
    stock + raptor, raptor bucketed by pow2-padded flight size so every
    bucket shares one compilation with masked-member waste under 2x."""
    cfgs = [dict(flight=int(c["flight"]), num_azs=int(c["num_azs"]),
                 rho=float(c.get("rho", 0.95)),
                 load=c.get("load", "medium")) for c in configs]
    # Table-6 overhead regimes are keyed by (ha, load) — a 1-AZ config in
    # the same sweep as HA configs must NOT inherit the HA overhead row
    oh = {(c["num_azs"] > 1, c["load"]): lognormal_params(
        *OverheadModel.TABLE[(c["num_azs"] > 1, c["load"])]) for c in cfgs}

    def oh_of(c):
        return oh[(c["num_azs"] > 1, c["load"])]

    tasks = []
    for f_pad, idxs in sorted(
            bucket_by_pad(c["flight"] for c in cfgs).items()):
        sub = [cfgs[i] for i in idxs]
        a_pad = max(c["num_azs"] for c in sub)
        tasks.append(SweepTask(
            "raptor", tuple(idxs),
            _open_raptor_core(int(trials), f_pad, wl.num_tasks, a_pad,
                              wl.dist, wl.fail_prob, wl.faults,
                              wl.recovery),
            jax.random.PRNGKey(seed * 2 + 1),
            (jnp.array([c["flight"] for c in sub]),
             jnp.array([c["num_azs"] for c in sub]),
             jnp.array([c["rho"] for c in sub]),
             jnp.array([oh_of(c)[0] for c in sub]),
             jnp.array([oh_of(c)[1] for c in sub])),
            (wl.mean_ms, wl.offset_ms, wl.cv, wl.stage_overhead_ms, 0.5)))
    tasks.append(SweepTask(
        "stock", tuple(range(len(cfgs))),
        _open_stock_core(int(trials), wl.num_tasks, wl.dist, wl.fail_prob,
                         wl.faults, wl.recovery),
        jax.random.PRNGKey(seed * 2),
        (jnp.array([c["rho"] for c in cfgs]),
         jnp.array([oh_of(c)[0] for c in cfgs]),
         jnp.array([oh_of(c)[1] for c in cfgs])),
        (wl.mean_ms, wl.offset_ms, wl.cv)))

    def finalize(cfg, parts):
        r = VectorResult(*parts["raptor"], True)
        s = VectorResult(*parts["stock"], False)
        res = dict(cfg)
        res["raptor"] = r.summary()
        res["stock"] = s.summary()
        res["mean_ratio"] = res["raptor"]["mean"] / res["stock"]["mean"]
        return res

    return SweepPlan("open-loop-pairs", cfgs, tasks, finalize)


# --------------------------------------------------------------------------
# closed-loop pairs (the sim/vector_queue.py family): traced rate/overhead
# --------------------------------------------------------------------------

# The closed-loop cores fuse the success-conditioned summary reduction
# (core.analytics.summarize_masked_batch) into the sharded program: every
# config's percentile sort runs on its own device and only eight scalars
# come home, so the grid's wall time actually scales with the mesh instead
# of serializing on per-config host round-trips.

@functools.lru_cache(maxsize=None)
def _queue_raptor_core(jobs, W, A, F, graph, dist, fail_prob,
                       faults, policy, block, resolver, scan,
                       summary_backend):
    from repro.core.analytics import summarize_masked_batch
    from repro.sim.vector_queue import _raptor_trial_fn
    trial = _raptor_trial_fn(jobs, W, A, F, graph, dist, fail_prob,
                             faults, policy, block, resolver, scan,
                             summary_backend)

    def core(keys, cfg, shared):
        rate, oh_mu, oh_sigma = cfg
        rho, means, offset, cv, stage_oh, slat = shared
        resp, ok, _ = jax.vmap(trial, in_axes=(0,) + (None,) * 9)(
            keys, rate, rho, means, offset, cv, stage_oh, slat,
            oh_mu, oh_sigma)
        return summarize_masked_batch(resp, ok)
    return core


@functools.lru_cache(maxsize=None)
def _queue_stock_core(jobs, W, A, graph, dist, fail_prob, faults,
                      policy, passes, has_extras, block, backend,
                      resolver, scan, summary_backend):
    from repro.core.analytics import summarize_masked_batch
    from repro.sim.vector_queue import _stock_trial_fn
    trial = _stock_trial_fn(jobs, W, A, graph, dist, fail_prob,
                            faults, policy, passes, has_extras, block,
                            backend, resolver, scan, summary_backend)

    def core(keys, cfg, shared):
        rate, oh_mu, oh_sigma = cfg
        rho, means, extras, offset, cv, stage_oh = shared
        resp, ok = jax.vmap(trial, in_axes=(0,) + (None,) * 9)(
            keys, rate, rho, means, extras, offset, cv, stage_oh,
            oh_mu, oh_sigma)
        return summarize_masked_batch(resp, ok)
    return core


def queue_pair_plan(sims, jobs: int, trials: int) -> SweepPlan:
    """A list of same-deployment ``QueueFlightSim``s as ONE closed-loop
    plan: arrival rate and the Table-6 overhead lognormal are the sharded
    config axes, stock and raptor each a single static-shape bucket.  This
    is the driver the fig6/fig7 load and utilisation grids run through —
    the dispatch-bound event scans are where device sharding pays
    near-linearly (see the module docstring).

    The substrate block configuration (``QueueFlightSim.engine_config``)
    is part of each bucket's static shape key alongside the padded event
    counts — sims sharing a plan must agree on it, or they could not share
    the bucket's compiled core."""
    s0 = sims[0]
    r_blk, r_res, r_scan = s0.engine_config("raptor")
    s_blk, s_res, s_scan = s0.engine_config("stock")
    for s in sims[1:]:
        if (s.engine_config("raptor") != (r_blk, r_res, r_scan)
                or s.engine_config("stock") != (s_blk, s_res, s_scan)
                or s.booking_backend != s0.booking_backend
                or s.summary_backend != s0.summary_backend):
            raise ValueError("sims in one queue plan must share the "
                             "substrate (block, resolver, scan, backend) "
                             "config — it is part of the bucket key")
        if s._fp != s0._fp or s._policy != s0._policy:
            # the fault environment and recovery policy are statics of
            # the compiled cores, so they join the bucket key too
            raise ValueError("sims in one queue plan must share the "
                             "fault profile and recovery policy — they "
                             "are statics of the bucket's compiled core")
    rates = jnp.array([s.rate_hz for s in sims])
    mus = jnp.array([s.oh_mu for s in sims])
    sigmas = jnp.array([s.oh_sigma for s in sims])
    wl = s0.wl
    all_idx = tuple(range(len(sims)))
    tasks = [
        SweepTask(
            "raptor", all_idx,
            _queue_raptor_core(
                int(jobs), s0.W, s0.A, s0.flight, wl.graph,
                wl.dist, wl.fail_prob, s0._fp, s0._policy,
                r_blk, r_res, r_scan, s0.summary_backend),
            s0._keys(trials, True),
            (rates, mus, sigmas),
            (s0.rho, jnp.asarray(wl.task_means, dtype=jnp.float32),
             wl.offset_ms, wl.cv, wl.raptor_stage_ms, s0.slat)),
        SweepTask(
            "stock", all_idx,
            _queue_stock_core(
                int(jobs), s0.W, s0.A, s0._sgraph,
                wl.dist, wl.fail_prob, s0._fp, s0._policy, s0._spasses,
                bool(s0._sextras.any()), s_blk, s0.booking_backend,
                s_res, s_scan, s0.summary_backend),
            s0._keys(trials, False),
            (rates, mus, sigmas),
            (s0.rho, jnp.asarray(s0._smeans), jnp.asarray(s0._sextras),
             wl.offset_ms, wl.cv, wl.stock_stage_ms)),
    ]

    def finalize(cfg, parts):
        def host(summ):
            return {k: (int(v) if k in ("n", "n_failed") else float(v))
                    for k, v in summ.items()}
        res = {"stock": host(parts["stock"]),
               "raptor": host(parts["raptor"])}
        res["mean_ratio"] = res["raptor"]["mean"] / res["stock"]["mean"]
        return res

    configs = [dict(rate_hz=s.rate_hz, load=s.load) for s in sims]
    return SweepPlan("queue-pairs", configs, tasks, finalize)
