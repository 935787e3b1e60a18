"""JAX-vectorized Monte-Carlo flight simulator: thousands of independent
invocations of the AZ-correlated service-time model at once.

The scalar :class:`repro.sim.flights.FlightSim` is an event-driven queueing
simulator — faithful, but minutes per configuration.  This module draws the
paper's correlation model (``Z = rho*S + (1-rho)*X``, S shared per AZ — see
``sim/cluster.py``) for a whole batch of trials as dense tensors and replays
each flight's race with a fixed-trip ``lax.scan`` under ``vmap``, so a
(flight size × AZ count × rho × load) sweep runs on-device in milliseconds.

Scope: this module is the OPEN-LOOP tier — independent-task manifests
(ssh-keygen, the Figure-8 reliability probes), one trial = one invocation
on an otherwise idle cluster, i.e. the zero-queueing limit of the scalar
sim.  The closed-loop tier lives in :mod:`repro.sim.vector_queue`: batched
M/G/c worker queues replayed over whole Poisson arrival streams, plus the
DAG manifests (wordcount, thumbnail) via per-member dependency masks — so
every load-dependent paper figure (fig6, fig7, Table 8 at real
utilisation) also runs on-device.  Config sweeps are batched in both
tiers and routed through the device-sharded driver in
:mod:`repro.sim.sweeps`: :func:`sweep_pairs` pads-and-masks over flight
size and traces rho/AZ-count/overhead so a whole (flight x AZ x rho x
load) grid shares a handful of compilations instead of paying an XLA
compile per point, with the config axis sharded over
the jax device mesh, and ``sequences="random"`` swaps the §3.3.3 cyclic
shifts for per-trial random orders (the ROADMAP F>>K paper-gap probe).
The scalar sim remains the oracle: ``tests/test_sim_vector.py`` and
``tests/test_sim_queue.py`` check seeded agreement on mean response,
tail percentiles, and failure rate from low through high utilisation.

Flight semantics mirror the scalar sim exactly (paper §3.3.3–§3.3.4):

* member ``m`` runs the task list cyclically shifted by ``m % num_tasks``;
* the first error-free completion of a task is broadcast, peers running it
  are preempted and restart after the half-RTT stream latency;
* a failed attempt is ignored by peers — the member simply moves on, and
  each member attempts a task at most once;
* the job fails only when every member has exhausted its sequence with some
  task still incomplete (``raptor_failure_exact``'s 1-(1-p^F)^K).

Stock (fork-join OpenWhisk) trials are closed-form on-device: one arrival
overhead plus the max of per-task independent service draws.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.analytics import (flight_fail_rate_batch,
                                  forkjoin_fail_rate_batch, summarize_batch)
from repro.sim.cluster import OverheadModel, lognormal_params
from repro.sim.faults import FaultProfile
from repro.sim.policies import (NO_RECOVERY, RecoveryPolicy, can_fail,
                                chain_transform)
from repro.sim.workloads import (KEYGEN_CV, KEYGEN_MEAN_MS, KEYGEN_OFFSET_MS,
                                 RELIABILITY_CV, RELIABILITY_MEAN_MS)


@dataclasses.dataclass(frozen=True)
class VectorWorkload:
    """Service-time model of one independent-task manifest (vector form)."""
    name: str
    num_tasks: int
    mean_ms: float
    offset_ms: float = 0.0
    dist: str = "exp"              # "exp" | "lognorm"
    cv: float = 1.0
    fail_prob: float = 0.0
    stage_overhead_ms: float = 0.5   # raptor stream hop per attempt
    # fault environment + recovery policy (frozen/hashable -> jit statics
    # and sweep bucket keys).  The open-loop tier models brownouts as a
    # stationary per-invocation snapshot and timeout/retry chains as a
    # draw transform (sim/policies.chain_transform); crash and hedge
    # semantics need wall-clock booking times -> closed-loop tier only
    faults: FaultProfile = None
    recovery: RecoveryPolicy = None


def keygen_vector(fail_prob: float = 0.0, faults: FaultProfile = None,
                  recovery: RecoveryPolicy = None) -> VectorWorkload:
    """ssh-keygen: two entropy-bound tasks, flight of 2 (Tables 7/8)."""
    return VectorWorkload("ssh-keygen", 2, KEYGEN_MEAN_MS, KEYGEN_OFFSET_MS,
                          "lognorm", KEYGEN_CV, fail_prob,
                          faults=faults, recovery=recovery)


def exponential_vector(num_tasks: int = 2, mean_ms: float = 1000.0,
                       fail_prob: float = 0.0, faults: FaultProfile = None,
                       recovery: RecoveryPolicy = None) -> VectorWorkload:
    """Pure exp(mu) tasks — the §4.2.1 theory's exact hypothesis, used to
    show the mutually-independent-exponential prediction emerge with scale."""
    return VectorWorkload(f"exp{num_tasks}", num_tasks, mean_ms, 0.0, "exp",
                          1.0, fail_prob, faults=faults, recovery=recovery)


def reliability_vector(n_tasks: int, fail_prob: float,
                       faults: FaultProfile = None,
                       recovery: RecoveryPolicy = None) -> VectorWorkload:
    """Figure 8's N parallel ~100ms busy-waits with injected task errors."""
    return VectorWorkload(f"busy{n_tasks}", n_tasks, RELIABILITY_MEAN_MS,
                          0.0, "lognorm", RELIABILITY_CV, fail_prob,
                          faults=faults, recovery=recovery)


def _stationary_deg(key, trials: int, num_azs: int, fp: FaultProfile):
    """(trials, A) stationary brownout snapshot; ``correlated`` draws ONE
    process and broadcasts it — the whole cluster degrades together."""
    pi = fp.stationary_degraded
    n = 1 if fp.correlated else num_azs
    d = jax.random.bernoulli(key, pi, (trials, n))
    return jnp.broadcast_to(d, (trials, num_azs)) if fp.correlated else d


# --------------------------------------------------------------------------
# on-device draw primitives (shared with sim/vector_queue.py)
# --------------------------------------------------------------------------

def unit_draws(key, shape, dist: str, cv):
    """Unit-mean service draws: exp(1), lognormal(mean=1, cv), or
    Pareto(mean=1, cv).

    ``cv`` may be traced.  Both vectorized tiers (this open-loop module and
    the closed-loop :mod:`repro.sim.vector_queue`) draw through this one
    helper so the service-time model cannot silently diverge between them.

    "pareto" is the heavy-tail family of the streaming traffic bank:
    classic Pareto(alpha, xm) with alpha = 1 + sqrt(1 + 1/cv^2) (always
    > 2, so mean and variance both exist and hit the requested cv) and
    xm = (alpha - 1)/alpha (unit mean), drawn by inversion
    X = xm * U^(-1/alpha).
    """
    if dist == "exp":
        return jax.random.exponential(key, shape)
    if dist == "pareto":
        alpha = 1.0 + jnp.sqrt(1.0 + 1.0 / (cv * cv))
        xm = (alpha - 1.0) / alpha
        u = jax.random.uniform(key, shape,
                               minval=jnp.finfo(jnp.float32).tiny)
        return xm * u ** (-1.0 / alpha)
    sigma2 = jnp.log1p(cv * cv)
    mu = -sigma2 / 2
    return jnp.exp(mu + jnp.sqrt(sigma2) * jax.random.normal(key, shape))


def _service_draws(key, shape, mean, dist: str, cv):
    return mean * unit_draws(key, shape, dist, cv)


def _overhead_draws(key, shape, med, p90):
    mu, sigma = lognormal_params(med, p90)    # med/p90 are static (Table 6)
    return jnp.exp(mu + sigma * jax.random.normal(key, shape))


# --------------------------------------------------------------------------
# one flight trial: fixed-trip event scan (vmapped over the batch)
# --------------------------------------------------------------------------

def _flight_trial(z_seq, fail_seq, t_join, seq, slat, active=None,
                  num_events: int = None):
    """Replay one flight race.

    Everything per-member is laid out in that member's *sequence order* so
    the scan body is pure one-hot arithmetic — per-trial dynamic gathers
    and scatters cripple the vmapped loop on the CPU backend.

    z_seq:    (F, K) attempt durations, z_seq[m, j] for task seq[m, j]
    fail_seq: (F, K) attempt-error indicators, same layout
    t_join:   (F,)   member join times (arrival control-plane overhead)
    seq:      (F, K) member task orders (cyclic shifts or per-trial perms)
    active:   (F,) bool or None — padding mask for the batched sweeps;
              inactive members never join (fin stays inf, no candidates)
    num_events: tighter exact scan budget when the caller can prove one —
              with ``fail_prob == 0`` every event is the completion of a
              *distinct* task (success broadcasts preempt any peer racing
              the same task before it could complete it again), so K
              events bound the race instead of the conservative F*K
              (tests/test_sim_vector.py pins exactness)
    Returns (response_time, ok).
    """
    F, K = z_seq.shape
    k_arange = jnp.arange(K)
    done0 = jnp.zeros(K, dtype=bool)
    attempted0 = jnp.zeros((F, K), dtype=bool).at[:, 0].set(True)
    if active is not None:
        attempted0 = attempted0 | ~active[:, None]
    cur0 = seq[:, 0]                      # current task id per member
    curfail0 = fail_seq[:, 0]             # whether that attempt will error
    fin0 = t_join + z_seq[:, 0]

    def step(carry, _):
        done, attempted, cur, curfail, fin, finished, ok, t_resp = carry
        active = ~jnp.isinf(fin)
        t = jnp.min(fin)                  # earliest finishing attempt
        e_hot = jnp.arange(F) == jnp.argmin(fin)
        task = jnp.sum(jnp.where(e_hot, cur, 0))
        succ = ~jnp.any(curfail & e_hot)
        done2 = done | ((k_arange == task) & succ)
        complete = jnp.all(done2)
        # the finisher always advances; on success, peers mid-`task` are
        # preempted by the broadcast and advance after the stream half-RTT
        preempted = succ & (cur == task) & active & ~e_hot
        adv = e_hot | preempted
        # next task per member: first in its shifted order that is neither
        # broadcast-complete nor already attempted by this member
        cand = (~done2[seq]) & (~attempted)
        has_next = jnp.any(cand, axis=1)
        j_hot = k_arange[None, :] == jnp.argmax(cand, axis=1)[:, None]
        nxt = jnp.sum(jnp.where(j_hot, seq, 0), axis=1)
        z_next = jnp.sum(jnp.where(j_hot, z_seq, 0.0), axis=1)
        start = jnp.where(e_hot, t, t + slat)
        fin2 = jnp.where(adv,
                         jnp.where(has_next, start + z_next, jnp.inf),
                         fin)
        cur2 = jnp.where(adv, jnp.where(has_next, nxt, -1), cur)
        curfail2 = jnp.where(adv,
                             jnp.any(j_hot & fail_seq, axis=1) & has_next,
                             curfail)
        attempted2 = attempted | (j_hot & (adv & has_next)[:, None])
        # terminal states: every task complete, or every member exhausted
        all_idle = jnp.all(jnp.isinf(fin2))
        terminal = (complete | all_idle) & ~finished
        # no per-element freeze needed past the terminal event: fin is all
        # inf and stays so (starts are priced off t = inf), so post-
        # terminal state drift cannot reach the latched ok/t_resp outputs
        carry2 = (done2, attempted2, cur2, curfail2, fin2,
                  finished | terminal,
                  jnp.where(terminal, complete, ok),
                  jnp.where(terminal, t, t_resp))
        return carry2, None

    carry0 = (done0, attempted0, cur0, curfail0, fin0,
              jnp.array(False), jnp.array(False), jnp.array(jnp.inf))
    # unrolling removes the scan's per-step dispatch overhead — the hot
    # path for small flights is a handful of steps
    steps = int(num_events) if num_events is not None else F * K
    (_, _, _, _, _, finished, ok, t_resp), _ = lax.scan(
        step, carry0, None, length=steps, unroll=min(steps, 8))
    return t_resp, ok


@functools.partial(
    jax.jit,
    static_argnames=("trials", "flight", "num_tasks", "num_azs", "dist",
                     "fail_prob", "oh_med", "oh_p90", "sequences",
                     "faults", "recovery"))
def _raptor_batch(key, *, trials, flight, num_tasks, num_azs, dist,
                  rho, mean, offset, cv, fail_prob, stage_oh, slat,
                  oh_med, oh_p90, sequences="cyclic", faults=None,
                  recovery=None):
    F, K, A = flight, num_tasks, num_azs
    fault_mode = ((faults is not None and faults.enabled)
                  or (recovery is not None and not recovery.is_default))
    pol = recovery if recovery is not None else NO_RECOVERY
    fp = faults if (faults is not None and faults.enabled) else None
    if fault_mode:
        if sequences == "random":
            k_z, k_f, k_o, k_q, k_d, k_e, k_j = jax.random.split(key, 7)
        else:
            k_z, k_f, k_o, k_d, k_e, k_j = jax.random.split(key, 6)
    elif sequences == "random":
        k_z, k_f, k_o, k_q = jax.random.split(key, 4)
    else:
        k_z, k_f, k_o = jax.random.split(key, 3)
    az = jnp.arange(F) % A                        # HA spread placement
    # one fused draw for the AZ-shared S block and the private X block —
    # threefry invocations dominate the batch cost on CPU
    sx = _service_draws(k_z, (trials, A + F, K), mean, dist, cv)
    s, x = sx[:, :A, :], sx[:, A:, :]
    z = rho * s[:, az, :] + (1 - rho) * x + offset + stage_oh
    # fail_prob is static so the p=0 common case folds the whole failure
    # path (and its uniform draw) out of the compiled scan
    if fault_mode:
        # stationary brownout snapshot per (trial, AZ) + the open-loop
        # chain transform: attempt durations inflate while degraded,
        # timeout/retry chains fold into per-attempt (duration, outcome)
        deg = (_stationary_deg(k_d, trials, A, fp) if fp is not None
               else jnp.zeros((trials, A), dtype=bool))
        deg_m = deg[:, az]                        # (trials, F) via placement
        R = pol.max_retries
        u_err = jax.random.uniform(k_e, (trials, F, K, R + 1))
        u_jit = jax.random.uniform(k_j, (trials, F, K, R))
        z, fail = chain_transform(z, u_err, u_jit, deg_m[:, :, None],
                                  policy=pol, faults=fp,
                                  base_fail=fail_prob)
    elif fail_prob == 0.0:
        fail = jnp.zeros((trials, F, K), dtype=bool)
    else:
        fail = jax.random.bernoulli(k_f, fail_prob, (trials, F, K))
    oh = _overhead_draws(k_o, (trials, F + 1), oh_med, oh_p90)
    oh0, ohm = oh[:, 0], oh[:, 1:]
    # member 0 joins at the arrival overhead; later members pay a second
    # control-plane hop (the fork's recursive invocation, §3.3.2)
    t_join = oh0[:, None] + jnp.where(jnp.arange(F) == 0, 0.0, ohm)
    # error-free races complete in exactly K events (see _flight_trial)
    anyfail = (can_fail(fail_prob, fp, pol) if fault_mode
               else fail_prob > 0.0)
    events = K if not anyfail else F * K
    if sequences == "random":
        # fresh uniform order per (trial, member) — the paper-gap probe for
        # the F >> K plateau (cyclic shifts duplicate orders; see ROADMAP)
        perm = jax.vmap(lambda k: jax.random.permutation(k, K))(
            jax.random.split(k_q, trials * F)).reshape(trials, F, K)
        z_seq = jnp.take_along_axis(z, perm, axis=2)
        fail_seq = jnp.take_along_axis(fail, perm, axis=2)
        t_resp, ok = jax.vmap(
            lambda zz, ff, tj, sq: _flight_trial(zz, ff, tj, sq, slat,
                                                 num_events=events))(
                z_seq, fail_seq, t_join, perm)
        return t_resp, ok, fail
    seq = jnp.stack([jnp.roll(jnp.arange(K), -(m % K)) for m in range(F)])
    # permute draws into sequence order once, outside the event scan
    seq_b = jnp.broadcast_to(seq, (trials, F, K))
    z_seq = jnp.take_along_axis(z, seq_b, axis=2)
    fail_seq = jnp.take_along_axis(fail, seq_b, axis=2)
    t_resp, ok = jax.vmap(
        lambda zz, ff, tj: _flight_trial(zz, ff, tj, seq, slat,
                                         num_events=events))(
            z_seq, fail_seq, t_join)
    return t_resp, ok, fail


def _stock_service_mix(key, trials, num_tasks, rho, mean, offset, dist, cv):
    """Stock per-task service times.  Distinct tasks never share an S draw
    (InvocationDraws keys S by (task, az)), but each task's time is still
    the rho-mixture of two i.i.d. draws — same mean, lighter tail than one
    raw draw; the p90/p99 comparisons against the scalar oracle are
    sensitive to this."""
    zz = _service_draws(key, (trials, 2, num_tasks), mean, dist, cv)
    return rho * zz[:, 0] + (1 - rho) * zz[:, 1] + offset


@functools.partial(
    jax.jit, static_argnames=("trials", "num_tasks", "num_azs", "dist",
                              "fail_prob", "oh_med", "oh_p90", "faults",
                              "recovery"))
def _stock_batch(key, *, trials, num_tasks, dist, rho, mean, offset, cv,
                 fail_prob, oh_med, oh_p90, num_azs=3, faults=None,
                 recovery=None):
    fault_mode = ((faults is not None and faults.enabled)
                  or (recovery is not None and not recovery.is_default))
    pol = recovery if recovery is not None else NO_RECOVERY
    fp = faults if (faults is not None and faults.enabled) else None
    if fault_mode:
        k_z, k_f, k_o, k_d, k_e, k_j = jax.random.split(key, 6)
    else:
        k_z, k_f, k_o = jax.random.split(key, 3)
    z = _stock_service_mix(k_z, trials, num_tasks, rho, mean, offset, dist,
                           cv)
    if fault_mode:
        # fork-join tasks spread round-robin over the AZs like the scalar
        # sim's worker pool; each folds its own timeout/retry chain
        deg = (_stationary_deg(k_d, trials, num_azs, fp) if fp is not None
               else jnp.zeros((trials, num_azs), dtype=bool))
        deg_t = deg[:, jnp.arange(num_tasks) % num_azs]
        R = pol.max_retries
        u_err = jax.random.uniform(k_e, (trials, num_tasks, R + 1))
        u_jit = jax.random.uniform(k_j, (trials, num_tasks, R))
        z, fail = chain_transform(z, u_err, u_jit, deg_t, policy=pol,
                                  faults=fp, base_fail=fail_prob)
    elif fail_prob == 0.0:
        fail = jnp.zeros((trials, num_tasks), dtype=bool)
    else:
        fail = jax.random.bernoulli(k_f, fail_prob, (trials, num_tasks))
    oh = _overhead_draws(k_o, (trials,), oh_med, oh_p90)
    t_resp = oh + jnp.max(z, axis=1)              # fork-join: wait for max
    ok = ~jnp.any(fail, axis=1)
    return t_resp, ok, fail


# --------------------------------------------------------------------------
# batched config sweeps: pad-and-mask over flight size, traced rho/AZ/load
# --------------------------------------------------------------------------
# sweep_scale() used to pay a full XLA compile per
# (flight, num_azs, rho, load) point because every knob was a static jit
# argument.  Here the knobs are *traced*: flights are padded to a common
# F_pad with inactive members masked out of the event scan, the AZ index is
# a gather from an A_pad-row shared block, and the Table-6 overhead enters
# as (mu, sigma) scalars — so one compilation serves the whole config grid
# via vmap, and adding a point costs milliseconds.

def _raptor_sweep_core(key, flight, num_azs, rho, mean, offset, cv,
                       stage_oh, slat, oh_mu, oh_sigma, *, trials,
                       flight_max, num_tasks, azs_max, dist, fail_prob,
                       faults=None, policy=None):
    F, K, A = flight_max, num_tasks, azs_max
    fault_mode = ((faults is not None and faults.enabled)
                  or (policy is not None and not policy.is_default))
    pol = policy if policy is not None else NO_RECOVERY
    fp = faults if (faults is not None and faults.enabled) else None
    if fault_mode:
        k_z, k_f, k_o, k_d, k_e, k_j = jax.random.split(key, 6)
    else:
        k_z, k_f, k_o = jax.random.split(key, 3)
    active = jnp.arange(F) < flight
    az = jnp.arange(F) % num_azs                  # traced AZ spread
    sx = _service_draws(k_z, (trials, A + F, K), mean, dist, cv)
    s, x = sx[:, :A, :], sx[:, A:, :]
    z = rho * s[:, az, :] + (1 - rho) * x + offset + stage_oh
    if fault_mode:
        deg = (_stationary_deg(k_d, trials, A, fp) if fp is not None
               else jnp.zeros((trials, A), dtype=bool))
        deg_m = deg[:, az]
        R = pol.max_retries
        u_err = jax.random.uniform(k_e, (trials, F, K, R + 1))
        u_jit = jax.random.uniform(k_j, (trials, F, K, R))
        z, fail = chain_transform(z, u_err, u_jit, deg_m[:, :, None],
                                  policy=pol, faults=fp,
                                  base_fail=fail_prob)
    elif fail_prob == 0.0:
        fail = jnp.zeros((trials, F, K), dtype=bool)
    else:
        fail = jax.random.bernoulli(k_f, fail_prob, (trials, F, K))
    oh = jnp.exp(oh_mu + oh_sigma * jax.random.normal(k_o, (trials, F + 1)))
    t_join = oh[:, :1] + jnp.where(jnp.arange(F) == 0, 0.0, oh[:, 1:])
    t_join = jnp.where(active, t_join, jnp.inf)   # padding: never joins
    seq = jnp.stack([jnp.roll(jnp.arange(K), -(m % K)) for m in range(F)])
    seq_b = jnp.broadcast_to(seq, (trials, F, K))
    z_seq = jnp.take_along_axis(z, seq_b, axis=2)
    fail_seq = jnp.take_along_axis(fail, seq_b, axis=2)
    anyfail = (can_fail(fail_prob, fp, pol) if fault_mode
               else fail_prob > 0.0)
    events = K if not anyfail else F * K
    t_resp, ok = jax.vmap(
        lambda zz, ff, tj: _flight_trial(zz, ff, tj, seq, slat, active,
                                         num_events=events))(
            z_seq, fail_seq, t_join)
    # a padded member's error draw never ran, so it must be neutral in the
    # all-attempts-errored reduction (flight_fail_rate_batch ANDs over the
    # flight axis): force it True, i.e. "contributes no rescue attempt"
    fail = fail | ~active[None, :, None]
    return t_resp, ok, fail


def _stock_sweep_core(key, rho, mean, offset, cv, oh_mu, oh_sigma, *,
                      trials, num_tasks, dist, fail_prob, num_azs=3,
                      faults=None, policy=None):
    fault_mode = ((faults is not None and faults.enabled)
                  or (policy is not None and not policy.is_default))
    pol = policy if policy is not None else NO_RECOVERY
    fp = faults if (faults is not None and faults.enabled) else None
    if fault_mode:
        k_z, k_f, k_o, k_d, k_e, k_j = jax.random.split(key, 6)
    else:
        k_z, k_f, k_o = jax.random.split(key, 3)
    z = _stock_service_mix(k_z, trials, num_tasks, rho, mean, offset, dist,
                           cv)
    if fault_mode:
        deg = (_stationary_deg(k_d, trials, num_azs, fp) if fp is not None
               else jnp.zeros((trials, num_azs), dtype=bool))
        deg_t = deg[:, jnp.arange(num_tasks) % num_azs]
        R = pol.max_retries
        u_err = jax.random.uniform(k_e, (trials, num_tasks, R + 1))
        u_jit = jax.random.uniform(k_j, (trials, num_tasks, R))
        z, fail = chain_transform(z, u_err, u_jit, deg_t, policy=pol,
                                  faults=fp, base_fail=fail_prob)
    elif fail_prob == 0.0:
        fail = jnp.zeros((trials, num_tasks), dtype=bool)
    else:
        fail = jax.random.bernoulli(k_f, fail_prob, (trials, num_tasks))
    oh = jnp.exp(oh_mu + oh_sigma * jax.random.normal(k_o, (trials,)))
    t_resp = oh + jnp.max(z, axis=1)
    ok = ~jnp.any(fail, axis=1)
    return t_resp, ok, fail


def pow2_pad(n: int) -> int:
    """Smallest power of two >= n — the pad-and-mask bucket width.

    Shared by every batched sweep that pads a ragged config axis (flight
    size here, event-stream length in the closed-loop tier): padding to the
    next power of two keeps the masked-compute waste under 2x while letting
    all configs in a bucket share one compilation.
    """
    return 1 << max(int(n) - 1, 0).bit_length()


def bucket_by_pad(sizes):
    """Group config indices by their pow2-padded size: {pad: [indices]}.

    One XLA compilation per bucket; a single global pad would make every
    small config pay for the largest one in the sweep.
    """
    buckets = {}
    for i, n in enumerate(sizes):
        buckets.setdefault(pow2_pad(n), []).append(i)
    return buckets


def sweep_pairs(wl: "VectorWorkload", configs, *, trials: int = 20_000,
                seed: int = 0, devices=None):
    """Run many (flight, num_azs, rho, load) points in ONE compile each for
    the raptor and stock paths.

    ``configs`` is a sequence of dicts with keys ``flight``, ``num_azs``,
    and optional ``rho`` (default 0.95) and ``load`` (default "medium").
    Returns one dict per config with stock/raptor summaries + mean ratio.

    A thin plan over the device-sharded sweep driver: the bucketing and
    pad-and-mask plumbing live in :mod:`repro.sim.sweeps`, and the config
    axis shards over ``devices`` (default: every jax device) with results
    bit-identical to the single-device run.
    """
    from repro.sim.sweeps import open_loop_pair_plan
    return open_loop_pair_plan(wl, configs, trials=trials,
                               seed=seed).run(devices=devices)


# --------------------------------------------------------------------------
# public driver
# --------------------------------------------------------------------------

@dataclasses.dataclass
class VectorResult:
    response_ms: jnp.ndarray     # (trials,)
    ok: jnp.ndarray              # (trials,) bool
    fail_draws: jnp.ndarray      # raptor (trials,F,K) / stock (trials,K)
    raptor: bool

    @property
    def trials(self) -> int:
        return int(self.response_ms.shape[0])

    def fail_rate(self) -> float:
        return float(1.0 - jnp.mean(self.ok))

    def theory_fail_rate(self) -> float:
        """Failure rate recomputed from the raw error draws on-device —
        cross-checks the event replay against the order-statistics form."""
        if self.raptor:
            return float(flight_fail_rate_batch(self.fail_draws))
        return float(forkjoin_fail_rate_batch(self.fail_draws))

    def summary(self) -> dict:
        """Delay summary conditioned on SUCCESS, failure accounting kept
        alongside.

        A failed job's "response" is its failure-*detection* time (every
        member exhausted), not a delay a client would see — mixing those
        into the percentiles biases the raptor summaries whenever
        ``fail_prob > 0``.  ``n`` counts the successful jobs summarized;
        ``n_failed`` and ``fail_rate`` carry the failure accounting.
        """
        ok = np.asarray(self.ok, dtype=bool)
        resp = np.asarray(self.response_ms)[ok]
        if resp.size:
            s = {k: (int(v) if k == "n" else float(v))
                 for k, v in summarize_batch(resp).items()}
        else:
            nan = float("nan")
            s = dict(mean=nan, median=nan, p90=nan, p99=nan, scv=nan, n=0)
        s["fail_rate"] = self.fail_rate()
        s["n_failed"] = int(ok.size - ok.sum())
        return s


class VectorFlightSim:
    """Batched Monte-Carlo of one (workload, deployment) configuration.

    Deployment knobs mirror :class:`repro.sim.cluster.Cluster`: AZ count
    (members are spread round-robin, the HA placement), correlation ``rho``,
    and the Table-6 control-plane overhead regime per (ha, load).
    """

    def __init__(self, wl: VectorWorkload, *, num_azs: int = 3,
                 flight: int = 2, rho: float = 0.95, load: str = "medium",
                 stream_latency_ms: float = 0.5, seed: int = 0,
                 sequences: str = "cyclic"):
        if sequences not in ("cyclic", "random"):
            raise ValueError(f"unknown sequences mode {sequences!r}")
        self.wl = wl
        self.num_azs = int(num_azs)
        self.flight = int(flight)
        self.rho = float(rho)
        self.load = load
        self.slat = float(stream_latency_ms)
        self.seed = int(seed)
        self.sequences = sequences
        ha = self.num_azs > 1
        self.oh_med, self.oh_p90 = OverheadModel.TABLE[(ha, load)]

    def _key(self, raptor: bool):
        return jax.random.PRNGKey(self.seed * 2 + (1 if raptor else 0))

    def run(self, trials: int = 10_000, *, raptor: bool = True) -> VectorResult:
        wl = self.wl
        if raptor:
            t, ok, fail = _raptor_batch(
                self._key(True), trials=int(trials), flight=self.flight,
                num_tasks=wl.num_tasks, num_azs=self.num_azs, dist=wl.dist,
                rho=self.rho, mean=wl.mean_ms, offset=wl.offset_ms,
                cv=wl.cv, fail_prob=wl.fail_prob,
                stage_oh=wl.stage_overhead_ms, slat=self.slat,
                oh_med=self.oh_med, oh_p90=self.oh_p90,
                sequences=self.sequences, faults=wl.faults,
                recovery=wl.recovery)
        else:
            t, ok, fail = _stock_batch(
                self._key(False), trials=int(trials),
                num_tasks=wl.num_tasks, dist=wl.dist, rho=self.rho,
                mean=wl.mean_ms, offset=wl.offset_ms, cv=wl.cv,
                fail_prob=wl.fail_prob,
                oh_med=self.oh_med, oh_p90=self.oh_p90,
                num_azs=self.num_azs, faults=wl.faults,
                recovery=wl.recovery)
        return VectorResult(t, ok, fail, raptor)

    def run_pair(self, trials: int = 10_000) -> Dict[str, dict]:
        """Stock + Raptor summaries and their mean ratio (Table-7 shape).

        The ratio divides the success-conditioned means (see
        :meth:`VectorResult.summary`), so injected failures perturb
        ``fail_rate``/``n_failed`` but never the delay comparison.
        """
        stock = self.run(trials, raptor=False)
        rap = self.run(trials, raptor=True)
        out = {"stock": stock.summary(), "raptor": rap.summary()}
        out["mean_ratio"] = out["raptor"]["mean"] / out["stock"]["mean"]
        return out
