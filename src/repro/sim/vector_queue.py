"""Closed-loop vectorized cluster engine: batched M/G/c worker queues and
DAG flights replayed on-device.

``sim/vector.py`` covers the open-loop zero-queueing limit — one invocation
on an idle cluster.  This module closes the loop: each trial replays a whole
Poisson arrival stream against a finite worker pool (the Table-6 overhead
regime's deployment), so the load-dependent paper figures (fig6's load ×
scale grid, fig7's DAG workloads, Table 8 at real utilisation) run as dense
tensors instead of crawling through the scalar event loop.

Structure (all on-device, ``vmap`` over trials and — for sweeps — configs):

* an outer ``lax.scan`` over arrival events carries the per-worker
  free-at-time vector; each arriving job claims workers (HA placement:
  member ``m`` waits for the earliest-free worker in AZ ``m % A``), races
  its flight, and scatters the member release times back into the pool;
* the flight race itself is a fixed-trip one-hot event scan like
  ``sim.vector._flight_trial``, extended with per-member dependency masks:
  a member whose next task in sequence has unmet dependencies parks
  (``fin = inf``) and is woken by the completion broadcast half an RTT
  later — wordcount and thumbnail manifests replay with the scalar
  ``FlightSim``'s §3.3.3/§3.3.4 semantics (cyclic-shift sequences from
  ``core.dag.execution_sequence``, head-of-line dependency waits,
  first-success broadcast preemption, at-most-one attempt per member);
* the stock path replays the fork-join at TASK granularity: every job's
  per-task ready-time streams (arrival + overhead for roots, dependency
  finish + storage hop + control-plane draw for staged tasks) are merged
  into ONE sorted event stream per trial, and the replay books a worker
  per *task* in ready order — the scalar oracle's task-level FCFS backlog.
  Staged ready times depend on queueing, so they are materialized by a
  bounded fixed point over stage depth (see ``_stock_trial_fn``);
  dep-free stock graphs are exact in one pass.

Both closed-loop replays run on the blocked event-replay substrate
(:mod:`repro.sim.scan_core`): the per-trial event stream is chunked into
blocks of ``block`` events, all bookings inside a block are resolved by a
bounded parallel fixed point over the worker free-at vector (raptor /
trace: the worker-identity Jacobi; stock measurement: the order-statistic
form), and only that W-vector crosses blocks — sequential depth drops
from O(jobs) to O(jobs/block · passes) while the intra-block work
vectorizes across the (trials × block) plane.  The DAG flight race rides
the same substrate: inside a block it runs once as a (block,)-wide batch
per fixed-point pass instead of once per job event.  ``block=1`` is
bit-for-bit the pre-blocking sequential scan and remains the oracle path
(tests/test_queue_properties.py pins block-size invariance); the default
resolves per engine and backend (``auto_config``): the fixed point is the
depth-reduction (accelerator) mode — its pass count tracks intra-block
queueing chains, which HA placement couples to whole cascades — and the
fused unrolled chunks are the host-throughput mode (EXPERIMENTS.md).

Arrival rate, rho, and the Table-6 overhead parameters are *traced*
arguments, so a whole load sweep shares one compilation via ``vmap`` over
the config axis (``sweep_runner``).

Fidelity notes (vs the scalar oracle, tests/test_sim_queue.py):

* staged stock ready times self-consistently converge through the bounded
  fixed point; with the default pass budget the wordcount stock path
  tracks the scalar task-FCFS oracle within 10% on mean AND p99 through
  util 0.75 (the regime where the old whole-job admission read ~4x
  pessimistic — ROADMAP's former known gap);
* the scalar sim draws ONE control-plane hop per stage-completion event
  (shared by every task it unblocks); the vector path draws one per
  unblocked task — same mean, negligibly lighter max over fan-outs;
* a dependency wait inside a flight ends exactly ``stream_latency_ms``
  after the unblocking broadcast (the scalar sim polls every half-RTT, so
  it lands within one poll of the same instant);
* with ``fail_prob > 0`` *and* dependencies, a fully-deadlocked flight
  (every member parked on a task whose attempts all errored) terminates
  with ``ok=False`` at its last event — the same convention the scalar
  sim now follows (``FlightSim._check_deadlock``), so every admitted job
  is accounted by BOTH engines and the scalar/vector agreement tests
  compare like with like (tests/test_sim_queue.py's deadlock test).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import obs
from repro.core.analytics import summarize_batch
from repro.core.workflow import WorkflowGraph, compile_spec, fanout, task
from repro.sim.cluster import OverheadModel, lognormal_params
from repro.sim.faults import (FaultProfile, first_start_in, interval_active,
                              push_out)
from repro.sim.policies import (NO_RECOVERY, RecoveryPolicy, can_fail,
                                fold_chain)
from repro.sim.scan_core import (blocked_bestfit_booking,
                                 blocked_event_replay, stock_booking_fins)
from repro.sim.vector import unit_draws
from repro.sim.workloads import (ETL_QUARANTINE_MS, KEYGEN_CV,
                                 KEYGEN_OFFSET_MS, THUMB_CV,
                                 THUMB_DOWNLOAD_MS, WC_STORAGE_HOP_MS,
                                 etl_graph, keygen_graph, mapreduce_graph,
                                 thumbnail_graph, thumbnail_stock_graph,
                                 wordcount_graph)
from repro.sim.workloads import arrival_rate_hz as _rate_for_load


@dataclasses.dataclass(frozen=True)
class QueueWorkload:
    """One compiled manifest bound to the vector engines' service model.

    ``graph`` is the workflow compiler's IR (:mod:`repro.core.workflow`):
    frozen and hashable, it IS the static key the cached trial builders
    and sweep bucket cores compile against — per-member sequences,
    dependency masks, and conditional select masks all derive from it.
    The stock graph may differ (thumbnail's stock functions re-download
    the source, so its task list drops the shared download stage and each
    task pays ``stock_extra_means`` as a second independent service
    draw); conditionals are always flattened for stock — the baseline has
    no data-dependent short-circuiting.
    """
    graph: WorkflowGraph
    flight: int
    dist: str = "exp"                       # "exp" | "lognorm" | "pareto"
    cv: float = 1.0
    offset_ms: float = 0.0
    raptor_stage_ms: float = 0.5            # stream hop per attempt
    stock: WorkflowGraph = None             # alternative stock-path graph
    stock_extra_means: Tuple[float, ...] = None
    stock_stage_ms: float = 0.0             # storage round-trip per stage hop
    fail_prob: float = 0.0
    work_est_ws: float = 2.0
    # fault environment + recovery policy carried with the workload (both
    # frozen/hashable, so they ride the static lru keys and the sweep
    # bucket keys); QueueFlightSim kwargs override
    faults: FaultProfile = None
    recovery: RecoveryPolicy = None

    @property
    def name(self) -> str:
        return self.graph.name

    @property
    def tasks(self) -> Tuple[str, ...]:
        return self.graph.tasks

    @property
    def task_means(self) -> Tuple[float, ...]:
        return self.graph.means

    def stock_graph(self) -> WorkflowGraph:
        g = self.stock if self.stock is not None else self.graph
        return g.flatten()

    def stock_extras(self) -> Tuple[float, ...]:
        if self.stock_extra_means is None:
            return (0.0,) * self.stock_graph().K
        return self.stock_extra_means


def keygen_queue(fail_prob: float = 0.0, faults: FaultProfile = None,
                 recovery: RecoveryPolicy = None) -> QueueWorkload:
    """ssh-keygen: two independent entropy-bound tasks, flight of 2."""
    return QueueWorkload(
        keygen_graph(), flight=2,
        dist="lognorm", cv=KEYGEN_CV, offset_ms=KEYGEN_OFFSET_MS,
        fail_prob=fail_prob, work_est_ws=1.9,
        faults=faults, recovery=recovery)


def wordcount_queue(fail_prob: float = 0.0, faults: FaultProfile = None,
                    recovery: RecoveryPolicy = None) -> QueueWorkload:
    """Map-reduce: split -> 4 maps -> reduce; stock pays the S3 hop."""
    return QueueWorkload(wordcount_graph(), flight=2,
                         dist="exp", stock_stage_ms=WC_STORAGE_HOP_MS,
                         fail_prob=fail_prob, work_est_ws=4.2,
                         faults=faults, recovery=recovery)


def thumbnail_queue(fail_prob: float = 0.0, faults: FaultProfile = None,
                    recovery: RecoveryPolicy = None) -> QueueWorkload:
    """Download + 4 resizes; stock functions each re-download the source."""
    return QueueWorkload(
        thumbnail_graph(), flight=4,
        dist="lognorm", cv=THUMB_CV,
        stock=thumbnail_stock_graph(),
        stock_extra_means=(THUMB_DOWNLOAD_MS,) * 4,
        fail_prob=fail_prob, work_est_ws=5.6,
        faults=faults, recovery=recovery)


def etl_queue(rank: int = 6, fail_prob: float = 0.08,
              faults: FaultProfile = None,
              recovery: RecoveryPolicy = None) -> QueueWorkload:
    """Workload-bank ETL pipeline (see :func:`repro.sim.workloads
    .etl_graph`): wide transform fan-out behind a ``validate`` guard
    whose outcome routes poison jobs to quarantine — the conditional
    mask-select path of the compiled IR.  ``fail_prob`` doubles as the
    poison rate."""
    g = etl_graph(rank)
    work = (sum(g.means) - ETL_QUARANTINE_MS) / 1000.0
    return QueueWorkload(g, flight=3, dist="exp",
                         stock_stage_ms=WC_STORAGE_HOP_MS,
                         fail_prob=fail_prob, work_est_ws=work,
                         faults=faults, recovery=recovery)


def mapreduce_queue(rank: int = 4, reducers: int = 2,
                    fail_prob: float = 0.0,
                    faults: FaultProfile = None,
                    recovery: RecoveryPolicy = None) -> QueueWorkload:
    """Workload-bank ranked map-reduce with a sync barrier (see
    :func:`repro.sim.workloads.mapreduce_graph`)."""
    g = mapreduce_graph(rank, reducers)
    return QueueWorkload(g, flight=3, dist="exp",
                         stock_stage_ms=WC_STORAGE_HOP_MS,
                         fail_prob=fail_prob,
                         work_est_ws=sum(g.means) / 1000.0,
                         faults=faults, recovery=recovery)


def heavytail_queue(num_tasks: int = 2, mean_ms: float = 1000.0,
                    flight: int = 2, cv: float = 2.5, dist: str = "pareto",
                    fail_prob: float = 0.0,
                    faults: FaultProfile = None,
                    recovery: RecoveryPolicy = None) -> QueueWorkload:
    """Heavy-tailed service family for the streaming traffic bank.

    ``dist`` picks the tail: "pareto" (power-law, alpha = 1 +
    sqrt(1 + 1/cv^2) > 2 so the mean load target still holds — see
    :func:`repro.sim.vector.unit_draws`) or "lognorm" at high cv.  Both
    keep unit mean, so ``work_est_ws`` and the UTIL load targets stay
    comparable with :func:`exponential_queue` at equal ``mean_ms``.
    """
    if dist not in ("pareto", "lognorm"):
        raise ValueError(
            f"heavy-tail dist must be 'pareto' or 'lognorm', got {dist!r}")
    if cv <= 0.0:
        raise ValueError(f"cv must be positive, got {cv}")
    return QueueWorkload(
        compile_spec(fanout(task("t", mean_ms), num_tasks),
                     name=f"{dist}{num_tasks}"),
        flight=flight, dist=dist, cv=cv, fail_prob=fail_prob,
        work_est_ws=num_tasks * mean_ms / 1000.0,
        faults=faults, recovery=recovery)


def exponential_queue(num_tasks: int = 2, mean_ms: float = 1000.0,
                      flight: int = 2, fail_prob: float = 0.0,
                      faults: FaultProfile = None,
                      recovery: RecoveryPolicy = None) -> QueueWorkload:
    """Pure exp(mu) independent tasks — the §4.2.1 theory's hypothesis."""
    return QueueWorkload(
        compile_spec(fanout(task("t", mean_ms), num_tasks),
                     name=f"exp{num_tasks}"),
        flight=flight, dist="exp", fail_prob=fail_prob,
        work_est_ws=num_tasks * mean_ms / 1000.0,
        faults=faults, recovery=recovery)


# --------------------------------------------------------------------------
# one flight race with dependency masks (the DAG-aware event scan)
# --------------------------------------------------------------------------

def dag_flight_trial(z_seq, fail_seq, t_join, seq, dep_mask, slat,
                     direct_start: bool = False, num_events: int = None,
                     no_failures: bool = False, recovery=None, cond=None):
    """Replay one flight of a (possibly DAG) manifest.

    Like ``sim.vector._flight_trial`` but members must respect ``dep_mask``
    ((K, K) bool, ``dep_mask[t, d]`` = task t needs task d): a member whose
    next task in sequence is not yet runnable parks (``fin = inf``) and is
    woken by the completion broadcast.  Member joins are modelled as events
    too (``cur = -1`` sentinel), so queue-delayed join times flow through
    the same scan.  Returns ``(t_resp, ok, t_release)`` with per-member
    worker release times (sequence exhausted, or flight end).

    ``direct_start=True`` (valid only when every member's first task is
    dependency-free and first tasks are member-distinct, so a late joiner
    can never find its first task already completed mid-flight) skips the
    F join events: members begin mid-attempt at ``t_join`` and the scan
    shrinks from F*(K+1) to F*K trips — the fast path for the fig6 sweep.

    ``num_events`` overrides the scan trip count with a tighter exact
    budget when the caller can prove one.  The load-bearing case: with
    ``fail_prob == 0`` every non-join event is the completion of a
    *distinct* task (a success broadcast preempts any peer mid-that-task,
    so no task completes twice, and a parked member's wake rides the
    completion event that unblocks it), so K completions + the F joins
    bound the replay — the closed-loop engines' races run at K instead of
    F*K trips, the hottest-loop win of the blocked rewrite
    (tests/test_queue_properties.py pins exactness against the full
    budget bitwise).

    ``no_failures=True`` (static) additionally drops the per-member
    attempted mask from the carry: an error-free attempt only ever ends
    because its task completed (by the member itself, or by the peer
    whose broadcast preempted it), so "attempted by me" implies "done"
    and the head-of-line candidate mask collapses to ``~done[seq]``.

    ``recovery`` (optional) is the fault/policy bundle ``(policy, faults,
    base_fail, bs, be, cs, ce, u_err, u_jit)``: per-member brownout
    tables of the PLACED AZ (``bs``/``be``, (F, I)), crash tables of the
    placed worker ((F, C)), and pre-drawn per-attempt uniforms
    ((F, K, R+1) errors / (F, K, R) backoff jitter).  Each launch then
    folds a whole timeout/retry/backoff chain into its ONE race event
    (``sim.policies.fold_chain``) — retries re-run on the same worker
    with the same service draw (deterministic re-execution), the member
    stays busy for the whole chain, and the first-success broadcast
    preempts a chain as a unit.  ``fail_seq`` is ignored in this mode
    (errors live in the fold's uniforms).

    ``cond`` (optional, static) is the compiled IR's conditional select
    pair ``(cond_guard, cond_sense)`` — per-task guard index (-1 =
    unconditional) and required guard outcome.  A guard task completes
    on its FIRST finished attempt whether or not that attempt erred
    (the error is the branch OUTCOME, not a job failure), and the same
    event mask-cancels every task gated on the opposite outcome: losers
    are marked done without consuming events, so the race budgets above
    still hold and the flight completes when the winning arm does.

    The event step is one-hot arithmetic only: ``seq``, ``dep_mask`` and
    ``cond`` are trace-time constants, so every lookup through them is a
    select over a constant one-hot table built once outside the scan —
    vmapped dynamic gathers (``done[seq]``, ``dep_mask[nxt]``) and the
    re-layouts of their small (F, K) tiles cripple the replay.
    """
    F, K = z_seq.shape
    if recovery is not None:
        (r_pol, r_fp, r_base_fail, r_bs, r_be, r_cs, r_ce,
         u_err, u_jit) = recovery
    seq_np = np.asarray(seq)
    dep_np = np.asarray(dep_mask)
    k_np = np.arange(K)
    # seq_hot[f, j, k]: member f's j-th task is k, so done[seq] is
    # any(seq_hot & done) over k
    seq_hot = jnp.asarray(seq_np[:, :, None] == k_np)
    # dep_mask is a trace-time constant (the manifest), so a dep-free
    # workload statically elides the runnable computation below
    has_deps = bool(dep_np.any())
    if has_deps:
        # dep_seq[f, j] = dep_mask[seq[f, j]]: the deps of member f's j-th
        # task, read at the step's one-hot next-task pick
        dep_seq = jnp.asarray(dep_np[seq_np])
    # likewise the conditional select masks: cond=None (or all -1)
    # compiles the exact pre-conditional jaxpr
    has_cond = cond is not None and any(g >= 0 for g in cond[0])
    if has_cond:
        c_gated = jnp.array([g >= 0 for g in cond[0]])
        c_guard_hot = jnp.asarray(
            np.maximum(np.asarray(cond[0]), 0)[:, None] == k_np)
        c_sense = jnp.array(list(cond[1]))
        c_is_guard = jnp.array(
            [k in {g for g in cond[0] if g >= 0} for k in range(K)])
    k_ar = jnp.arange(K)
    done0 = jnp.zeros(K, dtype=bool)
    released0 = jnp.zeros((F,), dtype=bool)
    trel0 = jnp.zeros((F,))
    if direct_start:
        attempted0 = jnp.zeros((F, K), dtype=bool).at[:, 0].set(True)
        cur0 = seq[:, 0]
        if recovery is None:
            curfail0 = fail_seq[:, 0]
            fin0 = t_join + z_seq[:, 0]
        else:
            fin0, curfail0 = fold_chain(
                t_join, z_seq[:, 0], u_err[:, 0], u_jit[:, 0],
                r_bs, r_be, r_cs, r_ce, policy=r_pol, faults=r_fp,
                base_fail=r_base_fail)
    else:
        attempted0 = jnp.zeros((F, K), dtype=bool)
        cur0 = jnp.full((F,), -1)
        curfail0 = jnp.zeros((F,), dtype=bool)
        fin0 = t_join
    if no_failures:
        attempted0 = None         # implied by `done` (see docstring)
    outcome0 = jnp.zeros(K, dtype=bool) if has_cond else None

    def step(carry, _):
        (done, attempted, outcome, cur, curfail, fin, released, trel,
         finished, ok, t_resp) = carry
        t = jnp.min(fin)
        e_hot = jnp.arange(F) == jnp.argmin(fin)
        any_busy = ~jnp.isinf(t)
        task = jnp.sum(jnp.where(e_hot, cur, 0))      # -1 on a join event
        raw_ok = ~jnp.any(curfail & e_hot)
        succ = any_busy & (task >= 0) & raw_ok
        if has_cond:
            # a guard's first finished attempt COMPLETES it either way;
            # the attempt's error bit becomes the recorded branch outcome
            ev_guard = jnp.any((k_ar == task) & c_is_guard)
            succ = succ | (any_busy & (task >= 0) & ev_guard)
            outcome = jnp.where((k_ar == task) & succ, raw_ok, outcome)
        done2 = done | ((k_ar == task) & succ)
        if has_cond:
            # mask-select: cancel the arm gated on the opposite outcome
            g_done = jnp.any(c_guard_hot & done2, axis=1)
            g_outcome = jnp.any(c_guard_hot & outcome, axis=1)
            cancel = c_gated & g_done & (g_outcome != c_sense)
            done2 = done2 | cancel
        busy = ~jnp.isinf(fin)
        # first-success broadcast preempts peers mid-`task` (§3.3.4)
        preempted = succ & (cur == task) & busy & ~e_hot
        freed = (e_hot & any_busy) | preempted
        busy_after = busy & ~freed
        idle = ~busy_after & ~released
        # next task per member: first in its shifted order neither complete
        # nor already attempted by this member (head-of-line: no skipping);
        # error-free attempts end only because their task completed, so
        # the attempted mask is implied by `done` and statically elided
        cand = ~jnp.any(seq_hot & done2, axis=2)
        if not no_failures:
            cand &= ~attempted
        has_next = jnp.any(cand, axis=1)
        j_hot = k_ar[None, :] == jnp.argmax(cand, axis=1)[:, None]
        nxt = jnp.sum(jnp.where(j_hot, seq, 0), axis=1)
        z_next = jnp.sum(jnp.where(j_hot, z_seq, 0.0), axis=1)
        can_start = idle & has_next
        if has_deps:
            # unmet[f, j]: member f's j-th task still waits on a dep
            unmet = jnp.any(dep_seq & ~done2, axis=2)
            can_start &= ~jnp.any(j_hot & unmet, axis=1)
        # the finisher chains immediately; preempted/woken members restart
        # after the stream half-RTT
        start = jnp.where(e_hot, t, t + slat)
        if recovery is None:
            f_next = jnp.any(j_hot & fail_seq, axis=1)
            fin_try = start + z_next
        else:
            # the whole timeout/retry/backoff chain is ONE event on the
            # member's placed worker; only the chain's final outcome is
            # visible to peers (§3.3.4)
            u_e = jnp.sum(jnp.where(j_hot[:, :, None], u_err, 0.0),
                          axis=1)
            u_j = jnp.sum(jnp.where(j_hot[:, :, None], u_jit, 0.0),
                          axis=1)
            fin_try, f_next = fold_chain(
                start, z_next, u_e, u_j, r_bs, r_be, r_cs, r_ce,
                policy=r_pol, faults=r_fp, base_fail=r_base_fail)
        fin2 = jnp.where(can_start, fin_try,
                         jnp.where(busy_after, fin, jnp.inf))
        cur2 = jnp.where(can_start, nxt, jnp.where(busy_after, cur, -1))
        curfail2 = jnp.where(can_start, f_next,
                             jnp.where(busy_after, curfail, False))
        attempted2 = (None if no_failures
                      else attempted | (j_hot & can_start[:, None]))
        newly_rel = idle & ~has_next
        released2 = released | newly_rel
        trel2 = jnp.where(newly_rel, t, trel)
        complete = jnp.all(done2)
        no_busy = jnp.all(jnp.isinf(fin2))
        terminal = (complete | no_busy) & ~finished
        trel2 = jnp.where(terminal & ~released2, t, trel2)
        released2 = released2 | terminal
        # no per-element freeze needed past the terminal event: fin is all
        # inf (so t = inf and nothing can start or newly release), done/
        # attempted/released are monotone, and the ok/t_resp outputs latch
        # on `terminal`, which `finished` stops from refiring
        carry2 = (done2, attempted2, outcome, cur2, curfail2, fin2,
                  released2, trel2, finished | terminal,
                  jnp.where(terminal, complete, ok),
                  jnp.where(terminal, t, t_resp))
        return carry2, None

    carry0 = (done0, attempted0, outcome0, cur0, curfail0, fin0, released0,
              trel0, jnp.array(False), jnp.array(False), jnp.array(jnp.inf))
    # F join events (unless direct_start) + at most F*K attempt completions
    steps = (int(num_events) if num_events is not None
             else (F * K if direct_start else F * (K + 1)))
    (_, _, _, _, _, _, _, trel, _, ok, t_resp), _ = lax.scan(
        step, carry0, None, length=steps, unroll=min(steps, 8))
    return t_resp, ok, trel


def _race_f2k2(z_seq, t_join):
    """Closed form of the error-free F=2, K=2 dep-free direct-start race —
    the Table-7/fig6 hot case (keygen, the exponential theory probes).

    With no failures and distinct first tasks there is exactly one event
    sequence shape: the earlier first-attempt completion (``t1``) marks
    its task done and its member chains IMMEDIATELY into the other task
    (start = t1, no stream hop — the finisher chains at the event time);
    the flight then completes at the earlier of the other member's
    first-attempt finish and that chained second attempt, and BOTH
    members release at the terminal event (the loser is preempted by the
    terminal broadcast mid-task, the winner releases on completion).  All
    three operations are the exact adds/selections the generic event scan
    performs, so this is bitwise the scan's result — pinned against the
    ``block=1`` oracle by tests/test_queue_properties.py.
    """
    f_first = t_join + z_seq[:, 0]
    t1 = jnp.min(f_first)
    f_other = jnp.max(f_first)
    e_hot = jnp.arange(2) == jnp.argmin(f_first)
    second = t1 + jnp.sum(jnp.where(e_hot, z_seq[:, 1], 0.0))
    t_resp = jnp.minimum(f_other, second)
    return t_resp, jnp.array(True), jnp.full((2,), t_resp)


# --------------------------------------------------------------------------
# closed-loop trial bodies (one whole arrival stream per trial)
# --------------------------------------------------------------------------

def auto_config(engine: str, scan: str = "auto") -> Tuple[int, str, str]:
    """Default (block, resolver, scan) per engine and backend.

    Measured on the recording box (EXPERIMENTS.md throughput-vs-B table):

    * the chain mode defaults to "seq" on every backend: the log-depth
      associative-summary chain re-resolves every block each outer pass,
      and under bitwise choice coupling the block-level Jacobi gains
      exactly ONE exact block per pass in every load regime
      (EXPERIMENTS.md §log-depth), so the mode is work-bound at >= 2x
      the sequential chain's bookings — an explicit opt-in
      (``scan="logdepth"``), not an auto pick;
    * raptor — bookings are placement-coupled (the chosen worker's AZ
      selects the shared service draws), so fixpoint passes track whole
      intra-block queueing cascades; hosts run fused unrolled blocks of
      8, accelerators the depth-reduced fixpoint;
    * stock — worker identity is interchangeable under ready-sorted
      FCFS, so the order-statistic fixpoint converges in a few passes;
      still, on CPU the sequential oracle already amortizes the dispatch
      cost the fixpoint exists to hide, so it stays default there.

    ``scan`` other than "auto" forces that chain mode and re-resolves
    the (block, resolver) defaults for it; the host log-depth block of
    0 is the adaptive split — ``ceil(n/3)`` at replay build time, two
    Jacobi blocks plus an equal ragged tail, the measured host optimum
    (larger ``nb`` multiplies work by the pass count, smaller wastes
    the tail's single resolve).
    """
    accel = jax.default_backend() not in ("cpu",)
    if scan == "auto":
        scan = "seq"
    if scan == "logdepth":
        return (64, "fixpoint", scan) if accel else (0, "unrolled", scan)
    if engine == "stock":
        return (64, "fixpoint", scan) if accel else (1, "fixpoint", scan)
    return (64, "fixpoint", scan) if accel else (8, "unrolled", scan)


def _raptor_mode(fail_prob: float, faults: FaultProfile,
                 policy: RecoveryPolicy):
    """Resolve the fault-branch statics shared by the whole-trace trial
    builder and the streaming microbatch stepper (one definition, so the
    two paths can never disagree on what flips the fault branch)."""
    fault_mode = ((faults is not None and faults.enabled)
                  or (policy is not None and not policy.is_default))
    pol = policy if policy is not None else NO_RECOVERY
    fp = faults if (faults is not None and faults.enabled) else None
    anyfail = (can_fail(fail_prob, fp, pol) if fault_mode
               else fail_prob > 0.0)
    return fault_mode, pol, fp, anyfail


def _raptor_env(fp: FaultProfile, k_b, k_c, A: int, W: int):
    """Exogenous fault environment: one brownout table per AZ, one crash
    table per worker (policy-only mode rides the inactive [inf, inf)
    sentinels).  Drawn per trial by the whole-trace replay and once per
    stream by the streaming scheduler."""
    if fp is not None:
        bs_az, be_az = fp.brownout_tables(k_b, A)
        cs_w, ce_w = fp.crash_tables(k_c, W)
    else:
        bs_az = be_az = jnp.full((A, 1), jnp.inf)
        cs_w = ce_w = jnp.full((W, 1), jnp.inf)
    return bs_az, be_az, cs_w, ce_w


def _raptor_job_draws(ks, arrivals, *, W, A, F, K, seq, dist, cv, rho,
                      means, offset, stage_oh, oh_mu, oh_sigma, fail_prob,
                      fault_mode, R):
    """Per-job event tensors for one batch of arrivals — the event pytree
    :func:`_raptor_job_body` books, WITHOUT the trial-level fault tables.
    Shared verbatim by the whole-trace trial and the streaming engine's
    per-microbatch draw, so the two paths produce identical event
    distributions by construction."""
    k_s, k_f, k_o, k_p, k_e, k_j = ks
    jobs = arrivals.shape[0]
    # one fused draw for the AZ-shared S block and the private X block
    # (threefry invocations dominate the batch cost on CPU)
    sx = unit_draws(k_s, (jobs, A + F, K), dist, cv)
    s, x = sx[:, :A, :], sx[:, A:, :]
    oh = jnp.exp(oh_mu + oh_sigma * jax.random.normal(k_o, (jobs, F + 1)))
    # member 0 pays the arrival overhead; later members a second
    # control-plane hop (the fork's recursive invocation, §3.3.2)
    t_oh = oh[:, :1] + jnp.where(jnp.arange(F) == 0, 0.0, oh[:, 1:])
    # The service mixture for EVERY possible member->AZ placement is
    # precomputed outside the replay — with the oracle's exact
    # arithmetic order per element, so the hot loop's one-hot row
    # select (an exact selection) leaves the blocked core bitwise the
    # sequential oracle.  (jobs, A, F, K): z_case[j, a, m] = member
    # m's sequence-ordered attempt times were it placed in AZ a.
    z_case = (rho * s[:, :, None, :] + (1 - rho) * x[:, None, :, :]) \
        * means + offset + stage_oh
    z_case = jnp.take_along_axis(
        z_case, jnp.broadcast_to(seq, (jobs, A, F, K)), axis=3)
    # placement tie-break randomness: the scalar sim picks uniformly
    # among the free (fresh-AZ-preferred) workers.  A deterministic
    # earliest-free pick keeps flight release pairs perfectly
    # anti-correlated across AZs and co-location never ignites — the
    # measured high-load colocation rate collapses to 0 vs the scalar
    # sim's ~13%, understating the correlation penalty.  One priority
    # vector per job is enough: members exclude each other's workers,
    # so the conditional pick stays uniform.
    prio = jax.random.uniform(k_p, (jobs, W))
    if fault_mode:
        # fault mode folds base errors into the per-attempt chain
        # uniforms — no precomputed outcome bitmap
        u_err = jax.random.uniform(k_e, (jobs, F, K, R + 1))
        u_jit = jax.random.uniform(k_j, (jobs, F, K, R))
        return (arrivals, z_case, t_oh, prio, u_err, u_jit)
    if fail_prob == 0.0:
        return (arrivals, z_case, t_oh, prio)
    fail = jax.random.bernoulli(k_f, fail_prob, (jobs, F, K))
    fail_seq = jnp.take_along_axis(fail, jnp.broadcast_to(
        seq, (jobs, F, K)), axis=2)
    return (arrivals, z_case, fail_seq, t_oh, prio)


def _raptor_race_budget(block: int, F: int, K: int, anyfail: bool,
                        fault_mode: bool, direct: bool, has_deps: bool):
    """(race_events, closed_form) for the flight race inside the replay.

    With no injected errors every race event is a distinct task
    completion, so K completions (+ the F joins when members cannot
    start mid-attempt) bound the race exactly (dag_flight_trial),
    and the F=2/K=2 dep-free case (the fig6 hot path) close-forms
    entirely (_race_f2k2).  The block=1 oracle path keeps the
    conservative full budget and the generic event scan for every
    workload; the invariance tests prove both reductions against it.
    """
    if block <= 1:
        return None, False
    race_events = (K if not anyfail else F * K) + (0 if direct else F)
    # the closed form knows nothing of inflation/crashes/timeouts,
    # so fault mode always runs the generic event scan
    closed_form = (F == 2 and K == 2 and not anyfail and not fault_mode
                   and direct and not has_deps)
    return race_events, closed_form


def _raptor_job_body(*, W, A, F, w_az, seq, dep_mask, slat, direct,
                     closed_form, race_events, fault_mode, anyfail,
                     fail_prob, pol, fp, has_failseq, env, trace,
                     cond=None):
    """The one-job booking body (HA placement + flight race) the blocked
    substrate replays — extracted from the whole-trace trial so the
    streaming scheduler books each microbatch with the *same* closure
    (bitwise: N microbatched steps carrying the W-state equal one
    whole-trace replay of the concatenated stream).

    ``env`` is the trial/stream-level fault-table bundle from
    :func:`_raptor_env` (``None`` outside fault mode)."""
    if fault_mode:
        bs_az, be_az, cs_w, ce_w = env
        bsW = jnp.take(bs_az, w_az, axis=0)            # (W, I) per worker
        beW = jnp.take(be_az, w_az, axis=0)

    K = seq.shape[1]

    def job_body(wfree, inp):
        if fault_mode:
            arrival, zcj, ohj, prj, u_e, u_j = inp
            fj = jnp.zeros((F, K), dtype=bool)
            # health snapshot at arrival: a worker is healthy iff its
            # AZ is not browned out when the flight places (the scalar
            # sim's _pick_worker_for health tier)
            hw = ~jnp.any((arrival >= bsW) & (arrival < beW), axis=1)
        elif not has_failseq:
            arrival, zcj, ohj, prj = inp
            fj = jnp.zeros((F, K), dtype=bool)
        else:
            arrival, zcj, fj, ohj, prj = inp
        # HA placement (scalar _pick_worker_for + backlog dispatch).
        # Free at arrival: pick a uniform-random free worker in an AZ
        # the flight hasn't used, else a uniform-random free worker.
        # Queued: the member never chooses — it is handed exactly the
        # next-released worker, whatever its AZ.  (Giving a queued
        # member AZ preference among simultaneously-released flight
        # pairs suppresses the scalar sim's ~13% high-load co-location
        # and with it the congestion the paper's Kafka-queue regime
        # shows — see tests/test_sim_queue.py.)
        # one-hot arithmetic only — vmapped dynamic gathers/scatters
        # (w_az[w], used_az.at[az], wf.at[w]) cripple the replay
        with obs.stage("placement"):
            wf = wfree
            fresh = jnp.ones(W, dtype=bool)      # workers in unused AZs
            t_disp, widx, m_az = [], [], []
            for m in range(F):
                t_any = jnp.min(wf)
                contended = t_any > arrival
                free = wf <= arrival
                elig = fresh & free
                if fault_mode:
                    # health-aware HA: healthy beats fresh beats neither
                    # (a browned-out AZ is skipped while ANY healthy free
                    # worker exists, and placement degrades gracefully to
                    # fewer zones when brownouts leave too few healthy);
                    # random-uniform within each tier, like the non-fault
                    # ranking below
                    key = jnp.where(free, prj + 2.0 * hw + 1.0 * fresh,
                                    -1.0)
                else:
                    # one argmax: fresh free workers rank in (1, 2], other
                    # free in (0, 1], busy at -1 — random-uniform per tier
                    key = jnp.where(elig, prj + 1.0,
                                    jnp.where(free, prj, -1.0))
                w = jnp.where(contended, jnp.argmin(wf), jnp.argmax(key))
                w_hot = jnp.arange(W) == w
                az = jnp.sum(jnp.where(w_hot, w_az, 0))
                fresh = fresh & (w_az != az)
                t_disp.append(jnp.maximum(arrival, t_any))
                widx.append(w)
                m_az.append(az)
                wf = jnp.where(w_hot, jnp.inf, wf)
            t_disp = jnp.stack(t_disp)
            widx = jnp.stack(widx)
            m_az = jnp.stack(m_az)
            # the AZ-shared S block follows the *actual* placement, so
            # co-located members (queue pressure) re-correlate like the
            # scalar sim; one-hot row select, no in-loop gathers
            az_hot = jnp.arange(A)[:, None] == m_az[None, :]     # (A, F)
            z_seq = jnp.sum(jnp.where(az_hot[:, :, None], zcj, 0.0),
                            axis=0)
        if fault_mode:
            # per-member fault tables follow the actual placement
            # (one-hot row selects — same no-gather discipline as the
            # service mixture above): brownouts of the placed AZ,
            # crashes of the placed worker
            wk_hot = jnp.arange(W)[None, :] == widx[:, None]  # (F, W)
            bs_m = jnp.sum(jnp.where(az_hot[:, :, None],
                                     bs_az[:, None, :], 0.0), axis=0)
            be_m = jnp.sum(jnp.where(az_hot[:, :, None],
                                     be_az[:, None, :], 0.0), axis=0)
            cs_m = jnp.sum(jnp.where(wk_hot[:, :, None],
                                     cs_w[None, :, :], 0.0), axis=1)
            ce_m = jnp.sum(jnp.where(wk_hot[:, :, None],
                                     ce_w[None, :, :], 0.0), axis=1)
            recovery = (pol, fp, fail_prob, bs_m, be_m, cs_m, ce_m,
                        u_e, u_j)
        else:
            recovery = None
        with obs.stage("race"):
            if closed_form:
                t_resp, ok, t_rel = _race_f2k2(z_seq, t_disp + ohj)
            else:
                t_resp, ok, t_rel = dag_flight_trial(
                    z_seq, fj, t_disp + ohj, seq, dep_mask, slat,
                    direct_start=direct, num_events=race_events,
                    no_failures=not anyfail, recovery=recovery, cond=cond)
        # the max-fold into the free-at vector guards the flight-
        # finished-before-dispatch case (the scalar sim skips the
        # dispatch; the worker was never taken); a padded (dead) job
        # must book nothing, so its releases are gated to -inf
        live = ~jnp.isinf(arrival)
        rel = jnp.where(live, t_rel, -jnp.inf)
        out = (t_resp - arrival, ok)
        if trace:
            out = out + (t_disp, widx, t_rel)
        return (widx, rel), out

    return job_body


@functools.lru_cache(maxsize=None)
def _raptor_trial_fn(jobs: int, W: int, A: int, F: int,
                     graph: WorkflowGraph, dist: str,
                     fail_prob: float, faults: FaultProfile = None,
                     policy: RecoveryPolicy = None, block: int = 1,
                     resolver: str = "fixpoint", scan: str = "seq",
                     summary_backend: str = "xla", trace: bool = False):
    """Per-trial closed-loop raptor replay, closed over the compiled IR.

    ``graph`` (a frozen :class:`repro.core.workflow.WorkflowGraph`) IS
    the static manifest key: member sequences, the dependency mask, and
    the conditional select masks all derive from it here, so
    content-equal compiled graphs share one cached executable.

    Traced args: arrival rate, rho, per-task means, offset, cv, stage
    overhead, stream latency, and the Table-6 lognormal (mu, sigma) — so a
    (load x rho) sweep vmaps over configs with one compilation.

    ``block``/``resolver`` chunk the arrival stream through the blocked
    substrate (:func:`repro.sim.scan_core.blocked_event_replay`): the
    fixpoint resolver re-books a whole block as one (block,)-wide batch
    per pass — exact because a job observes earlier jobs only through the
    max-plus worker free-at vector — while the unrolled resolver fuses
    each block into one straight-line region; blocked configs also run
    the races on the tight K-completion event budget.  ``scan``/
    ``summary_backend`` pick how resolved blocks chain ("seq" or the
    associative-summary "logdepth" mode).  ``block=1`` is the sequential
    oracle scan with the conservative full budget, bit-for-bit the
    pre-blocking engine.

    ``trace=True`` additionally returns ``(arrival, dispatch, worker,
    release)`` per (job, member) — the placement/booking trace the
    property-test harness checks worker-occupancy invariants on.

    ``faults``/``policy`` (static, hashable) switch on the fault branch:
    exogenous per-trial brownout/crash interval tables, per-attempt
    policy uniforms, health-aware HA placement, and the chain fold inside
    the race (``dag_flight_trial``'s ``recovery`` bundle).  Both ``None``
    (or disabled/default) compiles EXACTLY the pre-fault path — same key
    splits, same arithmetic, bit-for-bit.

    The draw stage (:func:`_raptor_job_draws`) and the booking body
    (:func:`_raptor_job_body`) are shared with the streaming scheduler
    (:func:`_raptor_stream_fns`), which replays the same body microbatch
    by microbatch on a persistent W-state.
    """
    fault_mode, pol, fp, anyfail = _raptor_mode(fail_prob, faults, policy)
    if not block:
        block = max(1, -(-jobs // 3))   # adaptive log-depth split
    K = graph.K
    seq_np = graph.member_sequences(F)
    seq = jnp.array(seq_np)
    dep_mask = jnp.array(graph.dep_mask())
    cond = graph.cond_static
    w_az = jnp.arange(W) % A
    # members may begin mid-attempt (no join events) only if a late joiner
    # can never find its first task already done while the flight still runs
    direct = (not graph.has_deps
              and len({int(s) for s in seq_np[:, 0]}) == F)
    race_events, closed_form = _raptor_race_budget(
        block, F, K, anyfail, fault_mode, direct, graph.has_deps)

    def trial(key, rate_hz, rho, means, offset, cv, stage_oh, slat,
              oh_mu, oh_sigma):
        if fault_mode:
            (k_a, k_s, k_f, k_o, k_p,
             k_b, k_c, k_e, k_j) = jax.random.split(key, 9)
        else:
            k_a, k_s, k_f, k_o, k_p = jax.random.split(key, 5)
            k_b = k_c = k_e = k_j = None
        with obs.stage("draws"):
            arrivals = jnp.cumsum(
                jax.random.exponential(k_a, (jobs,)) * (1000.0 / rate_hz))
            events = _raptor_job_draws(
                (k_s, k_f, k_o, k_p, k_e, k_j), arrivals, W=W, A=A, F=F,
                K=K, seq=seq, dist=dist, cv=cv, rho=rho, means=means,
                offset=offset, stage_oh=stage_oh, oh_mu=oh_mu,
                oh_sigma=oh_sigma, fail_prob=fail_prob,
                fault_mode=fault_mode, R=pol.max_retries)
        env = _raptor_env(fp, k_b, k_c, A, W) if fault_mode else None
        job_body = _raptor_job_body(
            W=W, A=A, F=F, w_az=w_az, seq=seq, dep_mask=dep_mask, slat=slat,
            direct=direct, closed_form=closed_form, race_events=race_events,
            fault_mode=fault_mode, anyfail=anyfail, fail_prob=fail_prob,
            pol=pol, fp=fp,
            has_failseq=(fail_prob > 0.0 and not fault_mode), env=env,
            trace=trace, cond=cond)
        # no padding: the substrate resolves a ragged tail as one final
        # partial block, so phantom jobs never enter the stream
        (_, passes), outs = blocked_event_replay(
            job_body, jnp.zeros(W), events, block=block, resolver=resolver,
            scan=scan, summary_backend=summary_backend)
        if trace:
            resp, ok, t_disp, widx, t_rel = outs
            return resp, ok, passes, (arrivals, t_disp, widx, t_rel)
        resp, ok = outs
        return resp, ok, passes

    return trial


@functools.lru_cache(maxsize=None)
def _raptor_stream_fns(W: int, A: int, F: int, graph: WorkflowGraph,
                       dist: str, fail_prob: float,
                       faults: FaultProfile = None,
                       policy: RecoveryPolicy = None, block: int = 1,
                       resolver: str = "fixpoint", scan: str = "seq",
                       summary_backend: str = "xla", trace: bool = False):
    """(draw_env, stream_draw, stream_step) for the streaming scheduler
    service; the two jitted ones run as ``jit_stream_draw`` and
    ``jit_stream_step``.

    The streaming engine (:mod:`repro.sim.streaming`) runs open arrivals
    against a *persistent* device-resident worker free-at vector: the host
    ingests/draws microbatch ``k+1`` while the device books microbatch
    ``k``, and only the W-vector survives between steps.  All three
    returned functions are jit-able and shape-polymorphic in the
    microbatch length:

    * ``draw_env(key) -> env`` — the stream-level fault-table bundle
      (:func:`_raptor_env`; drawn ONCE per stream — brownout/crash
      interval processes are exogenous wall-clock tables, exactly like
      the whole-trace replay's per-trial draw).  ``None`` outside fault
      mode.
    * ``stream_draw(key, arrivals, rho, means, offset, cv, stage_oh,
      oh_mu, oh_sigma) -> events`` — the per-job event tensors for one
      microbatch of (sorted, absolute-ms) arrival times
      (:func:`_raptor_job_draws`, the same draw the whole-trace trial
      performs).  Padded (``inf``) arrivals are dead events: they book
      nothing and leave the W-state bitwise untouched.
    * ``stream_step(wf, events, env, slat) -> (wf', outs)`` — book one
      microbatch through :func:`blocked_event_replay` with the SAME
      booking body as the whole-trace replay.  Because an event observes
      earlier events only through the carried W-vector, N consecutive
      ``stream_step`` calls over slices of a stream are bitwise-identical
      to one whole-trace replay of the concatenated stream (any block/
      resolver/scan config; tests/test_streaming.py pins this on runs AND
      traces, faults on and off).
    """
    fault_mode, pol, fp, anyfail = _raptor_mode(fail_prob, faults, policy)
    K = graph.K
    seq_np = graph.member_sequences(F)
    seq = jnp.array(seq_np)
    dep_mask = jnp.array(graph.dep_mask())
    cond = graph.cond_static
    w_az = jnp.arange(W) % A
    direct = (not graph.has_deps
              and len({int(s) for s in seq_np[:, 0]}) == F)

    def draw_env(key):
        if not fault_mode:
            return None
        k_b, k_c = jax.random.split(key)
        return _raptor_env(fp, k_b, k_c, A, W)

    def stream_draw(key, arrivals, rho, means, offset, cv, stage_oh,
                    oh_mu, oh_sigma):
        k_s, k_f, k_o, k_p, k_e, k_j = jax.random.split(key, 6)
        return _raptor_job_draws(
            (k_s, k_f, k_o, k_p, k_e, k_j), arrivals, W=W, A=A, F=F, K=K,
            seq=seq, dist=dist, cv=cv, rho=rho, means=means, offset=offset,
            stage_oh=stage_oh, oh_mu=oh_mu, oh_sigma=oh_sigma,
            fail_prob=fail_prob, fault_mode=fault_mode, R=pol.max_retries)

    def stream_step(wf, events, env, slat):
        mb = int(jax.tree_util.tree_leaves(events)[0].shape[0])
        blk = block if block else max(1, -(-mb // 3))
        race_events, closed_form = _raptor_race_budget(
            blk, F, K, anyfail, fault_mode, direct, graph.has_deps)
        job_body = _raptor_job_body(
            W=W, A=A, F=F, w_az=w_az, seq=seq, dep_mask=dep_mask,
            slat=slat, direct=direct, closed_form=closed_form,
            race_events=race_events, fault_mode=fault_mode,
            anyfail=anyfail, fail_prob=fail_prob, pol=pol, fp=fp,
            has_failseq=(fail_prob > 0.0 and not fault_mode), env=env,
            trace=trace, cond=cond)
        (wf, _), outs = blocked_event_replay(
            job_body, wf, events, block=blk, resolver=resolver, scan=scan,
            summary_backend=summary_backend)
        return wf, outs

    # jit HERE, inside the lru-cached factory: every StreamingScheduler
    # (and every oracle replay) of the same static config shares one
    # compiled executable instead of recompiling per engine instance.
    # The W-buffer is donated — the persistent state updates in place.
    return (draw_env, jax.jit(stream_draw),
            jax.jit(stream_step, donate_argnums=0))


@functools.lru_cache(maxsize=None)
def _stock_trial_fn(jobs: int, W: int, A: int, graph: WorkflowGraph,
                    dist: str, fail_prob: float,
                    faults: FaultProfile = None,
                    policy: RecoveryPolicy = None, passes: int = 1,
                    has_extras: bool = False, block: int = 1,
                    backend: str = "scan", resolver: str = "fixpoint",
                    scan: str = "seq",
                    summary_backend: str = "xla", trace: bool = False):
    """Per-trial closed-loop stock replay at TASK granularity (task FCFS).

    The scalar oracle's backlog is one FIFO of *tasks*: a task joins the
    queue the moment its stage hops elapse and takes the next worker, so at
    high load the stages of different jobs interleave freely.  This replay
    reproduces that discipline: all ``jobs * K`` per-task ready-time
    streams are merged into one sorted event stream and the blocked
    substrate books a worker per *task* in ready order (best-fit: the
    worker freed latest but still by the ready time, else the
    earliest-free — both are FCFS-equivalent under ready-sorted
    processing, best-fit keeps earlier idle holes open for the trace).
    ``block`` chunks that stream (``scan_core.stock_booking_fins``: the
    order-statistic fixed point, or the Pallas VMEM kernel when
    ``backend="pallas"``); the trace's final pass resolves worker ids
    through the generic fixed point at the same block size.  ``block=1``
    is bit-for-bit the pre-blocking sequential scan.

    Staged ready times depend on queueing (a map's ready is split's finish)
    so they are materialized by a bounded fixed point over stage depth:
    pass p schedules every task whose depth < p with the ready estimates of
    pass p-1; ``passes = depth + 1`` schedules everything, extra passes
    re-run the schedule with self-consistent estimates (dep-free graphs are
    exact in ONE pass; see ``QueueFlightSim.stock_extra_passes``).

    ``trace=True`` additionally returns ``(arrival, ready, start, fin,
    worker)`` — the booking trace the property-test harness (tests/
    test_queue_properties.py) checks invariants on; ``ready`` is the value
    the final scheduling pass actually honored.

    ``faults``/``policy`` (static, hashable) switch on the fault branch:
    every task expands into ``policy.stock_attempts`` attempt slots
    (primary + retries + the hedge copy), ALL slots join the one merged
    ready-sorted stream (unlaunched slots ride at ``ready = inf`` and
    book nothing), and each booking resolves its outcome against the
    per-trial brownout/crash tables.  Retry/hedge ready times depend on
    earlier bookings, so they materialize through the same bounded fixed
    point that stages already use (``QueueFlightSim`` scales ``passes``
    by the attempt budget).  Attempts reuse the task's service draw
    (deterministic re-execution — ``sim/policies.py``); the trace gains
    an attempt axis plus the per-attempt ``fail`` outcomes.  Both
    ``None`` (or disabled/default) compiles EXACTLY the pre-fault path.
    """
    K = graph.K
    dep_rows = np.array(graph.dep_mask(), dtype=bool)
    has_deps = bool(dep_rows.any())
    root = ~dep_rows.any(axis=1)
    dep_mask = jnp.array(dep_rows)
    root_j = jnp.array(root)
    fault_mode = ((faults is not None and faults.enabled)
                  or (policy is not None and not policy.is_default))
    pol = policy if policy is not None else NO_RECOVERY
    fp = faults if (faults is not None and faults.enabled) else None
    A_att = pol.stock_attempts if fault_mode else 1
    R = pol.max_retries
    N = jobs * K
    Na = N * A_att
    w_az = jnp.arange(W) % A
    if not block:
        block = max(1, -(-Na // 3))     # adaptive log-depth split

    def stock_trial(key, rate_hz, rho, means, extras, offset, cv, stage_oh,
                    oh_mu, oh_sigma):
        if fault_mode:
            (k_a, k_z, k_f, k_o,
             k_b, k_c, k_e, k_j) = jax.random.split(key, 8)
        else:
            k_a, k_z, k_f, k_o = jax.random.split(key, 4)
        arrivals = jnp.cumsum(
            jax.random.exponential(k_a, (jobs,)) * (1000.0 / rate_hz))
        # one fused draw for every service mixture (threefry invocations
        # dominate the batch cost on CPU).  Distinct tasks never share an
        # S draw, but each task's time is still the rho-mixture of two
        # i.i.d. draws — same mean, lighter tail than one raw draw (the
        # scalar sim's InvocationDraws.draw); workloads without a second
        # service component (``has_extras``) statically skip its draws.
        zz = unit_draws(k_z, (jobs, 4 if has_extras else 2, K), dist, cv)
        z = (rho * zz[:, 0] + (1 - rho) * zz[:, 1]) * means + offset
        if has_extras:
            z = z + (rho * zz[:, 2] + (1 - rho) * zz[:, 3]) * extras
        if fault_mode:
            ok = None        # derived from the attempt outcomes below
        elif fail_prob == 0.0:
            ok = jnp.ones((jobs,), dtype=bool)
        else:
            ok = ~jnp.any(jax.random.bernoulli(k_f, fail_prob, (jobs, K)),
                          axis=1)
        oh = jnp.exp(oh_mu + oh_sigma * jax.random.normal(k_o,
                                                          (jobs, K + 1)))
        oh0, ohd = oh[:, 0], oh[:, 1:]
        # roots queue after the arrival overhead; staged tasks are inf until
        # a fixed-point pass materializes their dependencies' finish times
        ready0 = jnp.where(root_j[None, :],
                           arrivals[:, None] + oh0[:, None], jnp.inf)
        z_flat = z.reshape(N)
        if fault_mode:
            # exogenous fault environment (policy-only mode rides the
            # inactive sentinels) + per-attempt policy uniforms; the
            # service draw is shared across a task's attempts
            # (deterministic re-execution)
            if fp is not None:
                bs_az, be_az = fp.brownout_tables(k_b, A)
                cs_w, ce_w = fp.crash_tables(k_c, W)
            else:
                bs_az = be_az = jnp.full((A, 1), jnp.inf)
                cs_w = ce_w = jnp.full((W, 1), jnp.inf)
            bsW = jnp.take(bs_az, w_az, axis=0)        # (W, I) per worker
            beW = jnp.take(be_az, w_az, axis=0)
            u_err = jax.random.uniform(k_e, (jobs, K, A_att))
            u_jit = jax.random.uniform(k_j, (jobs, K, R))
            infl = fp.degraded_inflation if fp is not None else 1.0
            pdeg = fp.degraded_fail_prob if fp is not None else fail_prob
            z_att = jnp.broadcast_to(z[:, :, None], (jobs, K, A_att))

        def book(ready, full):
            # ONE merged event stream: every task of every job, ready
            # order.  The sort need not be stable: exact ties only occur
            # among one job's dep-free roots (shared arrival + oh0), whose
            # service draws are i.i.d. symmetric, so the FCFS order among
            # them is statistically irrelevant (the scalar sim pushes them
            # in task-list order).  No padding: the substrate resolves a
            # ragged tail as one final partial block.
            order = jnp.argsort(ready.reshape(N), stable=False)
            r_s = ready.reshape(N)[order]
            z_s = z_flat[order]
            if not full:
                # the stage-depth fixed point only consumes finish times;
                # start/worker are resolved on the trace's final pass (each
                # dropped output is a (jobs*K,) scatter saved per pass)
                fins, = stock_booking_fins(jnp.zeros(W), r_s, z_s,
                                           block=block, backend=backend,
                                           scan=scan,
                                           summary_backend=summary_backend)
                return (jnp.zeros(N).at[order].set(fins[:N])
                        .reshape(jobs, K), None, None)
            fins, sts, wks = blocked_bestfit_booking(
                jnp.zeros(W), r_s, z_s, block=block, full=True,
                backend=backend, scan=scan,
                summary_backend=summary_backend)
            f = jnp.zeros(N).at[order].set(fins[:N]).reshape(jobs, K)
            st = jnp.zeros(N).at[order].set(sts[:N]).reshape(jobs, K)
            wk = jnp.zeros(N, jnp.int32).at[order].set(
                wks[:N]).reshape(jobs, K)
            return f, st, wk

        def refresh(fin):
            # stage hops (storage round-trip + control-plane draw) elapse
            # BEFORE a worker is occupied — FlightSim._stock_enqueue_ready
            dmax = jnp.max(jnp.where(dep_mask[None, :, :],
                                     fin[:, None, :], -jnp.inf), axis=2)
            return jnp.where(root_j[None, :], ready0,
                             dmax + stage_oh + ohd)

        if fault_mode:
            def book_f(att_ready):
                # joint task-FCFS over every attempt slot: one merged
                # ready-sorted stream of jobs*K*A_att events; unlaunched
                # slots ride at ready=inf and book nothing (dead events)
                order = jnp.argsort(att_ready.reshape(Na), stable=False)
                r_s = att_ready.reshape(Na)[order]
                z_s = z_att.reshape(Na)[order]
                u_s = u_err.reshape(Na)[order]

                def att_body(wf, inp):
                    r, zb, u = inp
                    live = ~jnp.isinf(r)
                    # per-worker start were the attempt booked there: the
                    # free-at/ready floor pushed past the worker's crash
                    # outages; earliest start wins, exact ties broken
                    # toward healthy AZs then lowest index — the oracle's
                    # lexicographic (start, degraded, w) dispatch key.  A
                    # flat additive penalty cannot express this in fp32:
                    # at 1e5 ms the spacing is ~8e-3, so any penalty small
                    # enough not to flip genuine orderings is absorbed
                    stw = push_out(jnp.maximum(wf, r), cs_w, ce_w)
                    deg_w = interval_active(stw, bsW, beW)
                    tie = stw == jnp.min(stw)
                    w = jnp.argmin(jnp.where(
                        tie, deg_w.astype(stw.dtype), jnp.inf))
                    w_hot = jnp.arange(W) == w
                    s = jnp.sum(jnp.where(w_hot, stw, 0.0))
                    deg = jnp.any(w_hot & deg_w)
                    zi = zb * jnp.where(deg, infl, 1.0)
                    dur = jnp.minimum(zi, pol.timeout_ms)
                    p_err = jnp.where(deg, pdeg, fail_prob)
                    cs_sel = jnp.sum(jnp.where(w_hot[:, None], cs_w, 0.0),
                                     axis=0)
                    c1 = first_start_in(s, s + dur, cs_sel)
                    crashed = c1 < s + dur
                    end = jnp.where(crashed, c1, s + dur)
                    fl = (u < p_err) | (zi > pol.timeout_ms) | crashed
                    rel = jnp.where(live, end, -jnp.inf)
                    return (w[None], rel[None]), (end, s, fl, w)

                _, outs = blocked_event_replay(
                    att_body, jnp.zeros(W), (r_s, z_s, u_s), block=block,
                    resolver=resolver, scan=scan,
                    summary_backend=summary_backend)
                fins, sts, fls, wks = outs

                def unsort(v, dtype=None):
                    buf = (jnp.zeros(Na) if dtype is None
                           else jnp.zeros(Na, dtype))
                    return (buf.at[order].set(v[:Na])
                            .reshape(jobs, K, A_att))
                return (unsort(fins), unsort(sts), unsort(fls, bool),
                        unsort(wks, jnp.int32))

            def task_outcomes(fin_a, fl_a):
                booked = ~jnp.isinf(fin_a)
                succ = booked & ~fl_a
                any_s = jnp.any(succ, axis=2)
                fin_s = jnp.min(jnp.where(succ, fin_a, jnp.inf), axis=2)
                # a task dies once its retry chain is spent: the LAST
                # chain attempt launched and failed (any launched hedge
                # also failed, else any_s); detection = latest attempt end
                dead = booked[:, :, R] & fl_a[:, :, R]
                fin_d = jnp.max(jnp.where(booked, fin_a, -jnp.inf),
                                axis=2)
                tfin = jnp.where(any_s, fin_s,
                                 jnp.where(dead, fin_d, jnp.inf))
                return tfin, any_s

            def fault_ready(fin_a, st_a, fl_a, base_r):
                # attempt 0 queues at the task's stage ready; retry r
                # queues backoff after attempt r-1's failure; the hedge
                # copy queues hedge_ms after attempt 0 started iff the
                # primary is still running then (outcomes are pre-
                # resolved, so the gate is exact — no cancellation)
                booked = ~jnp.isinf(fin_a)
                cols = [base_r]
                for a in range(1, pol.chain_attempts):
                    prev = booked[:, :, a - 1] & fl_a[:, :, a - 1]
                    back = pol.backoff_ms * (2.0 ** (a - 1)) * (
                        1.0 + pol.backoff_jitter * u_jit[:, :, a - 1])
                    cols.append(jnp.where(
                        prev, fin_a[:, :, a - 1] + back, jnp.inf))
                if pol.has_hedge:
                    st0, fin0 = st_a[:, :, 0], fin_a[:, :, 0]
                    cols.append(jnp.where(
                        booked[:, :, 0] & (fin0 > st0 + pol.hedge_ms),
                        st0 + pol.hedge_ms, jnp.inf))
                return jnp.stack(cols, axis=2)

            att_ready = jnp.concatenate(
                [ready0[:, :, None],
                 jnp.full((jobs, K, A_att - 1), jnp.inf)], axis=2)
            for p in range(passes):
                fin_a, st_a, fl_a, wk_a = book_f(att_ready)
                tfin, any_s = task_outcomes(fin_a, fl_a)
                if p + 1 < passes:
                    base_r = refresh(tfin) if has_deps else ready0
                    att_ready = fault_ready(fin_a, st_a, fl_a, base_r)
            okf = jnp.all(any_s, axis=1)
            resp = jnp.max(tfin, axis=1) - arrivals
            if trace:
                # the drawn fault tables ride along so the property-test
                # harness can check bookings against the outages they
                # were scheduled around
                return resp, okf, (arrivals, att_ready, st_a, fin_a,
                                   wk_a, fl_a, cs_w, ce_w, bs_az, be_az)
            return resp, okf

        ready = ready0
        for p in range(passes):
            fin, start, wkr = book(ready, trace and p + 1 == passes)
            if has_deps and p + 1 < passes:
                ready = refresh(fin)
        resp = jnp.max(fin, axis=1) - arrivals
        if trace:
            return resp, ok, (arrivals, ready, start, fin, wkr)
        return resp, ok

    return stock_trial


@functools.lru_cache(maxsize=None)
def _raptor_runner(jobs, W, A, F, graph, dist, fail_prob,
                   faults: FaultProfile = None,
                   policy: RecoveryPolicy = None,
                   block: int = 1, resolver: str = "fixpoint",
                   scan: str = "seq", summary_backend: str = "xla",
                   trace: bool = False):
    """Jitted (trials,)-vmapped raptor runner, cached so repeated ``run()``
    calls reuse the compiled executable, ``jit_trial``: per trial the
    responses, ``ok`` bits and fixpoint pass counts per block.  Config
    sweeps no longer live here: the device-sharded driver
    (:mod:`repro.sim.sweeps`) vmaps the same per-trial body over the
    config axis and shards it over the mesh.
    """
    trial = _raptor_trial_fn(jobs, W, A, F, graph, dist,
                             fail_prob, faults, policy, block, resolver,
                             scan, summary_backend, trace)
    return jax.jit(jax.vmap(trial, in_axes=(0,) + (None,) * 9))


@functools.lru_cache(maxsize=None)
def _stock_runner(jobs, W, A, graph, dist, fail_prob,
                  faults: FaultProfile = None,
                  policy: RecoveryPolicy = None, passes: int = 1,
                  has_extras: bool = False, block: int = 1,
                  backend: str = "scan", resolver: str = "fixpoint",
                  scan: str = "seq",
                  summary_backend: str = "xla", trace: bool = False):
    trial = _stock_trial_fn(jobs, W, A, graph, dist, fail_prob,
                            faults, policy, passes, has_extras, block,
                            backend, resolver, scan,
                            summary_backend, trace)
    return jax.jit(jax.vmap(trial, in_axes=(0,) + (None,) * 9))


# --------------------------------------------------------------------------
# public driver
# --------------------------------------------------------------------------

@dataclasses.dataclass
class QueueResult:
    response_ms: jnp.ndarray     # (trials, jobs)
    ok: jnp.ndarray              # (trials, jobs) bool
    raptor: bool
    # (trials, blocks) int32: the Jacobi passes each trial's blocks took
    # (scan_core's fixpoint resolver on the "seq" chain); None on every
    # other engine configuration.  Left on the device.
    fixpoint_passes: Optional[jnp.ndarray] = None

    @property
    def jobs(self) -> int:
        return int(self.response_ms.size)

    def fail_rate(self) -> float:
        return float(1.0 - jnp.mean(self.ok))

    def summary(self) -> dict:
        """Delay summary conditioned on SUCCESS (a failed job's "response"
        is its failure-detection time, not a client-visible delay), with
        the failure accounting alongside: ``n`` counts the successful jobs
        summarized, ``n_failed``/``fail_rate`` the rest."""
        ok = np.asarray(self.ok, dtype=bool).ravel()
        resp = np.asarray(self.response_ms).ravel()[ok]
        if resp.size:
            s = {k: (int(v) if k == "n" else float(v))
                 for k, v in summarize_batch(resp).items()}
        else:
            nan = float("nan")
            s = dict(mean=nan, median=nan, p90=nan, p99=nan, scv=nan, n=0)
        s["fail_rate"] = self.fail_rate()
        s["n_failed"] = int(ok.size - ok.sum())
        return s


class QueueFlightSim:
    """Closed-loop batched Monte-Carlo of one (workload, deployment) pair.

    One *trial* is a whole replication of the queue: ``jobs`` Poisson
    arrivals contending for ``num_workers`` workers spread over ``num_azs``
    AZs, starting empty (like the scalar sim's measurement window).
    """

    def __init__(self, wl: QueueWorkload, *, num_workers: int = 15,
                 num_azs: int = 3, flight: int = None, rho: float = 0.95,
                 load: str = "medium", arrival_rate_hz: float = None,
                 stream_latency_ms: float = 0.5, seed: int = 0,
                 stock_extra_passes: int = 1, block: int = None,
                 resolver: str = "auto", scan: str = "auto",
                 booking_backend: str = "scan",
                 summary_backend: str = "xla",
                 faults: FaultProfile = None,
                 recovery: RecoveryPolicy = None):
        """``stock_extra_passes``: extra fixed-point iterations of the
        task-FCFS stock schedule beyond the ``stage_depth + 1`` needed to
        materialize every ready time.  Dep-free stock graphs (keygen,
        thumbnail) are exact in one pass and ignore this; for staged graphs
        (wordcount) each extra pass re-sorts the merged event stream with
        self-consistent ready estimates — wordcount at util 0.75 already
        sits within ~1% of the scalar oracle at 0 extras and is converged
        at 1 (tests/test_sim_queue.py).

        ``block``/``resolver``/``scan``: the blocked event-replay
        configuration (``sim/scan_core.py``).  Results are block-size,
        resolver, and scan-mode invariant (bitwise —
        tests/test_queue_properties.py), so these are pure performance
        knobs: ``block=None``/``resolver="auto"``/``scan="auto"``
        resolves per engine and backend via :func:`auto_config`;
        ``block=1`` forces the sequential oracle scan (conservative race
        budget — bit-for-bit the pre-blocking engine); larger blocks run
        the chunked substrate with ``resolver`` "fixpoint" (bounded
        parallel fixed point, the depth-reduction mode) or "unrolled"
        (fused sequential chunks), chained either sequentially
        (``scan="seq"``) or through the associative max-plus summary
        prefix (``scan="logdepth"`` — O(log nb) depth per outer Jacobi
        pass; work-bound on hosts, see EXPERIMENTS.md §log-depth).
        ``booking_backend``: "scan" (the jnp substrate) or "pallas" (the
        fused VMEM booking kernel, ``repro.kernels.queue_booking``) for
        the stock stream; ``summary_backend`` routes the log-depth
        summary prefix ("xla" or the ``repro.kernels.maxplus_scan``
        VMEM kernel).

        ``faults``/``recovery``: the fault environment
        (:class:`repro.sim.faults.FaultProfile`) and attempt-level
        policy (:class:`repro.sim.policies.RecoveryPolicy`); ``None``
        defaults from the workload's own fields, explicit kwargs win.
        An enabled profile or non-default policy flips both engines onto
        the fault branch (still block/resolver/scan invariant, bitwise);
        it is incompatible with ``booking_backend="pallas"``, whose
        fused kernel books plain FCFS finishes only."""
        self._id = obs.next_id()
        with obs.span("build", id=self._id):
            self.wl = wl
            self.W = int(num_workers)
            self.A = int(num_azs)
            self.flight = int(flight if flight is not None else wl.flight)
            if self.flight > self.W:
                # the placement loop hands each member a distinct worker; more
                # members than workers would dispatch at argmin(all-inf) = inf
                raise ValueError(
                    f"flight={self.flight} needs distinct workers but the "
                    f"deployment has only num_workers={self.W}")
            self.rho = float(rho)
            self.load = load
            self.slat = float(stream_latency_ms)
            self.seed = int(seed)
            self.rate_hz = float(
                arrival_rate_hz if arrival_rate_hz is not None
                else _rate_for_load(wl.work_est_ws, self.W, load))
            # offered utilisation (service work / capacity), for reference and
            # for sizing windows; the substrate config resolves per engine
            self.utilization = self.rate_hz * wl.work_est_ws / self.W
            self._block = None if block is None else int(block)
            self.resolver = str(resolver)
            self.scan = str(scan)
            self.booking_backend = str(booking_backend)
            self.summary_backend = str(summary_backend)
            self.faults = faults if faults is not None else wl.faults
            self.recovery = (recovery if recovery is not None
                             else (wl.recovery if wl.recovery is not None
                                   else NO_RECOVERY))
            # statics handed to the cached trial builders: None unless they
            # change behavior, so disabled profiles share the pre-fault
            # compile cache entries (and their bitwise output)
            self._fp = (self.faults if (self.faults is not None
                                        and self.faults.enabled) else None)
            self.fault_mode = (self._fp is not None
                               or not self.recovery.is_default)
            self._policy = self.recovery if self.fault_mode else None
            if self.fault_mode and self.booking_backend == "pallas":
                raise ValueError(
                    "booking_backend='pallas' books plain FCFS finish times "
                    "only; fault injection needs the generic scan substrate")
            ha = self.A > 1
            self.oh_mu, self.oh_sigma = lognormal_params(
                *OverheadModel.TABLE[(ha, load)])
            # static manifest prep: both engines' sequences/masks/levels now
            # come straight off the compiled IR (repro.core.workflow) — the
            # graph objects themselves are the cached builders' static keys
            self._sgraph = wl.stock_graph()
            self._smeans = np.asarray(self._sgraph.means, dtype=np.float32)
            self._sextras = np.asarray(wl.stock_extras(), dtype=np.float32)
            # fixed-point pass budget for the task-FCFS stock replay: depth+1
            # passes materialize every ready time, extras refine the estimates
            self._sdepth = self._sgraph.stage_depth()
            if self.fault_mode:
                # the retry/hedge readies materialize through the same
                # bounded fixed point as staged readies: each stage level
                # needs its whole attempt chain resolved before dependents'
                # estimates settle, so the pass budget scales by the
                # per-task attempt count
                self._spasses = ((self._sdepth + 1)
                                 * self.recovery.stock_attempts
                                 + int(stock_extra_passes))
            else:
                self._spasses = (1 if self._sdepth == 0
                                 else self._sdepth + 1
                                 + int(stock_extra_passes))

    # -- compiled runners ------------------------------------------------
    def engine_config(self, engine: str) -> Tuple[int, str, str]:
        """Resolved (block, resolver, scan) for ``engine``
        ("raptor"/"stock"): explicit constructor knobs win, the rest
        comes from :func:`auto_config`'s measured per-backend policy
        (forcing ``scan`` re-resolves the defaults for that chain mode)."""
        blk, res, sc = auto_config(engine, self.scan)
        if self._block is not None:
            blk = self._block
        if self.resolver != "auto":
            res = self.resolver
        return blk, res, sc

    def _raptor_fn(self, jobs: int, trace: bool = False):
        blk, res, sc = self.engine_config("raptor")
        return _raptor_runner(
            int(jobs), self.W, self.A, self.flight, self.wl.graph,
            self.wl.dist, self.wl.fail_prob, self._fp, self._policy,
            blk, res, sc, self.summary_backend, trace)

    def _stock_fn(self, jobs: int, trace: bool = False):
        blk, res, sc = self.engine_config("stock")
        return _stock_runner(
            int(jobs), self.W, self.A, self._sgraph,
            self.wl.dist, self.wl.fail_prob, self._fp, self._policy,
            self._spasses, bool(self._sextras.any()), blk,
            self.booking_backend, res, sc, self.summary_backend, trace)

    def _raptor_args(self):
        wl = self.wl
        return (self.rate_hz, self.rho,
                jnp.asarray(wl.task_means, dtype=jnp.float32), wl.offset_ms,
                wl.cv, wl.raptor_stage_ms, self.slat,
                self.oh_mu, self.oh_sigma)

    def _stock_args(self):
        wl = self.wl
        return (self.rate_hz, self.rho, jnp.asarray(self._smeans),
                jnp.asarray(self._sextras), wl.offset_ms, wl.cv,
                wl.stock_stage_ms, self.oh_mu, self.oh_sigma)

    def _keys(self, trials: int, raptor: bool):
        with obs.span("keys", id=self._id):
            base = jax.random.PRNGKey(self.seed * 2 + (1 if raptor else 0))
            return jax.random.split(base, trials)

    def run(self, jobs: int = 1024, trials: int = 16, *,
            raptor: bool = True) -> QueueResult:
        """Dispatch ``trials`` trials of ``jobs`` arrivals; the result's
        arrays stay on the device.  Host spans (:mod:`repro.core.obs`):
        ``sim.build`` (the constructor), ``sim.keys`` (the trial keys) and
        ``sim.dispatch`` (the runner's lookup and enqueue), all carrying
        this simulator's ``id``."""
        keys = self._keys(trials, raptor)
        with obs.span("dispatch", id=self._id):
            if raptor:
                fn = self._raptor_fn(jobs)
                resp, ok, passes = fn(keys, *self._raptor_args())
            else:
                fn = self._stock_fn(jobs)
                resp, ok = fn(keys, *self._stock_args())
                passes = None
        return QueueResult(resp, ok, raptor, passes)

    def run_pair(self, jobs: int = 1024, trials: int = 16) -> Dict[str, dict]:
        stock = self.run(jobs, trials, raptor=False)
        rap = self.run(jobs, trials, raptor=True)
        out = {"stock": stock.summary(), "raptor": rap.summary()}
        out["mean_ratio"] = out["raptor"]["mean"] / out["stock"]["mean"]
        return out

    def trace_run(self, jobs: int = 256, trials: int = 4, *,
                  raptor: bool = True) -> Dict[str, np.ndarray]:
        """Replay with the booking trace exposed (host numpy arrays).

        Stock: per-(trial, job, task) ``ready`` (the value the final
        scheduling pass honored), ``start``, ``fin``, ``worker``.  Raptor:
        per-(trial, job, member) ``dispatch``/``worker``/``release`` — the
        worker-occupancy intervals.  The property-test harness
        (tests/test_queue_properties.py) checks queue invariants on these;
        same seeds as :meth:`run`, so the traced replay IS the measured
        one.
        """
        if raptor:
            fn = self._raptor_fn(jobs, trace=True)
            resp, ok, _, (arr, disp, widx, rel) = fn(
                self._keys(trials, True), *self._raptor_args())
            return {"response": np.asarray(resp), "ok": np.asarray(ok),
                    "arrival": np.asarray(arr),
                    "dispatch": np.asarray(disp),
                    "worker": np.asarray(widx),
                    "release": np.asarray(rel)}
        fn = self._stock_fn(jobs, trace=True)
        if self.fault_mode:
            # fault-mode stock traces carry the attempt axis (jobs, K,
            # A_att) plus the per-attempt failure outcomes; an unlaunched
            # attempt slot shows ready/start/fin = inf.  The per-trial
            # fault tables ((W, C) crash and (A, I) brownout intervals)
            # ride along for outage-aware invariant checks.
            resp, ok, (arr, ready, start, fin, wkr, fl,
                       cs, ce, bs, be) = fn(
                self._keys(trials, False), *self._stock_args())
            return {"response": np.asarray(resp), "ok": np.asarray(ok),
                    "arrival": np.asarray(arr),
                    "ready": np.asarray(ready),
                    "start": np.asarray(start), "fin": np.asarray(fin),
                    "worker": np.asarray(wkr), "fail": np.asarray(fl),
                    "crash_start": np.asarray(cs),
                    "crash_end": np.asarray(ce),
                    "az_start": np.asarray(bs), "az_end": np.asarray(be)}
        resp, ok, (arr, ready, start, fin, wkr) = fn(
            self._keys(trials, False), *self._stock_args())
        return {"response": np.asarray(resp), "ok": np.asarray(ok),
                "arrival": np.asarray(arr), "ready": np.asarray(ready),
                "start": np.asarray(start), "fin": np.asarray(fin),
                "worker": np.asarray(wkr)}


# --------------------------------------------------------------------------
# batched config sweeps: thin plans over the device-sharded driver
# --------------------------------------------------------------------------
# Arrival rate and the Table-6 overhead lognormal are traced, so the config
# axis is pure batching; repro.sim.sweeps vmaps it and shards it over the
# device mesh (bit-identical to the single-device run) — adding a point
# costs milliseconds, not a recompile, and a multi-device host runs the
# grid near-linearly faster.

def load_sweep(wl: QueueWorkload, *, num_workers: int = 15, num_azs: int = 3,
               loads=("low", "medium", "high"), rho: float = 0.95,
               jobs: int = 1024, trials: int = 16,
               seed: int = 0, devices=None) -> Dict[str, dict]:
    """All Table-6 load points of one deployment, one compile per mode."""
    from repro.sim.sweeps import queue_pair_plan
    sims = [QueueFlightSim(wl, num_workers=num_workers, num_azs=num_azs,
                           load=load, rho=rho, seed=seed) for load in loads]
    return dict(zip(loads,
                    queue_pair_plan(sims, jobs, trials).run(devices=devices)))


def rate_sweep(wl: QueueWorkload, rates_hz, *, loads=None,
               num_workers: int = 15, num_azs: int = 3, rho: float = 0.95,
               jobs: int = 1024, trials: int = 16, seed: int = 0,
               devices=None):
    """Arbitrary arrival-rate grid (continuous load axis) on one
    deployment; ``loads`` optionally names the Table-6 overhead regime per
    point (defaults to "medium").  Returns one pair dict per rate."""
    from repro.sim.sweeps import queue_pair_plan
    loads = list(loads) if loads is not None else ["medium"] * len(rates_hz)
    sims = [QueueFlightSim(wl, num_workers=num_workers, num_azs=num_azs,
                           load=load, rho=rho, arrival_rate_hz=float(r),
                           seed=seed)
            for r, load in zip(rates_hz, loads)]
    return queue_pair_plan(sims, jobs, trials).run(devices=devices)
