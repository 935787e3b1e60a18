"""Blocked event-replay substrate: chunked max-plus scans over a worker pool.

Every closed-loop engine in :mod:`repro.sim.vector_queue` replays one
sorted event stream per trial against a pool of ``W`` workers, carrying the
per-worker free-at-time vector through a ``lax.scan`` — O(events) of
*sequential* depth that no amount of trial-vmapping or device sharding
(PR 4) can hide, because every step is a tiny dispatch-bound op.  This
module cuts that depth by the block size: the stream is chunked into blocks
of ``B`` events, all bookings inside a block are resolved by a bounded
parallel fixed point, and only the W-vector crosses block boundaries.

Why a fixed point suffices (the blocked max-plus recurrence, derived in
EXPERIMENTS.md):

* an event's booking depends on earlier events ONLY through the worker
  free-at vector ``wf`` it observes, and every booking enters ``wf`` as a
  per-worker **max** (release times on one worker are non-decreasing in
  booking order, so max == overwrite) — a max-plus update;
* therefore the vector event ``i`` observes is reconstructible from the
  block-entry vector plus the bookings of events ``j < i`` alone:
  ``wf_i = max(wf_in, max_{j<i} contrib_j)`` — an *exclusive running max*
  over the block, computable for every event at once (``lax.cummax``);
* that dependency is strictly lower-triangular in the event order, so the
  Jacobi iteration "re-book every event against the vectors reconstructed
  from the previous pass" has a UNIQUE fixed point — the sequential
  schedule itself — and after pass ``p`` the first ``p`` events are exact.
  ``B`` passes are thus always enough (the bound), and the loop exits as
  soon as one pass changes nothing (typically ~(block bookings)/W + 1
  passes: the longest same-worker chain inside the block).

The intra-block work is (B x W) dense arithmetic vectorized across the
(trials x B) plane; sequential depth drops from O(events) to
O(events/B * passes).  ``block=1`` degenerates to the plain event scan
(bit-for-bit the pre-blocking engines) and is kept as the oracle path.

Chaining blocks is itself a max-plus linear recurrence: a resolved block
maps the incoming W-vector by a factored operator (diag, offset) that
composes associatively (``maxplus_compose``), so ``scan="logdepth"``
replaces the O(N/B) sequential block scan with ONE
``lax.associative_scan`` over block summaries per outer pass — O(log N/B)
sequential depth, with a block-level Jacobi (same lower-triangularity
argument, now in block index) supplying exact entry vectors in at most
N/B outer passes.  The summary build + compose also ships as a Pallas
kernel (:mod:`repro.kernels.maxplus_scan`) that keeps the whole operator
tape VMEM-resident on accelerators.

The fused best-fit/earliest-free booking step additionally ships as a
Pallas kernel (:mod:`repro.kernels.queue_booking`) so accelerator runs
resolve whole blocks in VMEM instead of round-tripping HBM per event;
:func:`blocked_bestfit_booking` routes between the two backends.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.obs import stage


def booking_contrib(num_workers: int, widx, rel):
    """Dense (..., W) max-map of one event's bookings.

    ``widx``/``rel`` are the event's booked worker indices and release
    times, shape (..., M); a negative index (dead/padded booking) matches
    no worker and contributes ``-inf`` everywhere.  One-hot arithmetic
    only — per-trial dynamic scatters cripple the vmapped replay on CPU.
    """
    with stage("booking"):
        oh = widx[..., None] == jnp.arange(num_workers)
        return jnp.max(jnp.where(oh, rel[..., None], -jnp.inf), axis=-2)


def apply_bookings(wf, widx, rel):
    """Fold one event's bookings into the free-at vector (max-plus)."""
    with stage("booking"):
        return jnp.maximum(wf, booking_contrib(wf.shape[-1], widx, rel))


def exclusive_running_max(contrib, wf_in):
    """Per-event observed W-vectors: row ``i`` is ``max(wf_in,
    max_{j<i} contrib[j])`` — the worker vector event ``i`` would see had
    events ``0..i-1`` booked exactly ``contrib[0..i-1]``."""
    with stage("booking"):
        run = lax.cummax(contrib, axis=0)
        prev = jnp.concatenate(
            [jnp.full((1,) + run.shape[1:], -jnp.inf, run.dtype), run[:-1]],
            axis=0)
        return jnp.maximum(wf_in[None, :], prev)


# --------------------------------------------------------------------------
# factored W x W max-plus block operators (the log-depth summaries)
# --------------------------------------------------------------------------
# A resolved block acts on the carried free-at vector as a max-plus linear
# map.  In full generality that map is a W x W matrix, but every map the
# replay produces factors as (diag, offset): apply((d, b), wf) =
# max(wf + d, b) elementwise — the diagonal shifts what the block leaves of
# the incoming vector, the offset is the block's own bookings.  Factored
# operators compose closed-form in O(W) (compose below) and the composition
# is associative, so a whole stream's prefix maps come out of ONE
# `lax.associative_scan` at O(log nb) sequential depth.
#
# Bitwise note: the engines only ever emit diag = 0 operators (a booking
# REPLACES a worker's free-at time; it never shifts it), and with d == 0
# the compose degenerates to an elementwise float max — exactly
# associative in floats, which is what lets scan="logdepth" stay bitwise
# against the sequential oracle.  The general d != 0 form is kept (and
# property-tested) because it is the algebra the Pallas kernel implements.

def maxplus_identity(num_workers: int, dtype=jnp.float32):
    """The do-nothing block operator: d = 0, b = -inf."""
    return (jnp.zeros((num_workers,), dtype),
            jnp.full((num_workers,), -jnp.inf, dtype))


def maxplus_compose(first, then):
    """Operator for "apply ``first``, then ``then``" (elementwise, O(W)).

    ``apply(compose(first, then), wf) == apply(then, apply(first, wf))``:
    max(max(wf + d1, b1) + d2, b2) = max(wf + (d1 + d2), max(b1 + d2, b2)).
    """
    d1, b1 = first
    d2, b2 = then
    return d1 + d2, jnp.maximum(b1 + d2, b2)


def maxplus_apply(op, wf):
    """Push a free-at vector through a factored block operator."""
    d, b = op
    return jnp.maximum(wf + d, b)


def block_summary(num_workers: int, widx, rel):
    """Offset part of a resolved block's operator: the per-worker max of
    its booking contributions, shape (..., W) from (..., B, M) estimates.
    The engines' diagonal part is identically 0 (see module note)."""
    return jnp.max(booking_contrib(num_workers, widx, rel), axis=-2)


def maxplus_prefix_entries(diag, off, wf0, *, backend: str = "xla",
                           interpret=None):
    """Entry vectors of every block from one associative prefix scan.

    ``diag``/``off``: (nb, W) factored per-block operators, ``wf0``: (W,)
    the stream's entry vector.  Returns ``(entries, wf_out)``: row ``k``
    of ``entries`` (nb, W) is the vector block ``k`` begins with —
    ``apply(op_0 ∘ … ∘ op_{k-1}, wf0)``, row 0 is ``wf0`` itself — and
    ``wf_out`` is the whole stream's exit vector.  ``backend="pallas"``
    routes through :mod:`repro.kernels.maxplus_scan` (the VMEM-resident
    doubling scan); ``"xla"`` is ``jax.lax.associative_scan``.
    """
    if backend == "pallas":
        from repro.kernels.maxplus_scan.ops import maxplus_entries
        ent, wf_out = maxplus_entries(diag[None], off[None], wf0[None],
                                      interpret=interpret)
        return ent[0], wf_out[0]
    if backend != "xla":
        raise ValueError(f"unknown summary backend {backend!r}")
    pd, pb = lax.associative_scan(maxplus_compose, (diag, off), axis=0)
    entries = jnp.concatenate(
        [wf0[None], maxplus_apply((pd[:-1], pb[:-1]), wf0[None])], axis=0)
    return entries, maxplus_apply((pd[-1], pb[-1]), wf0)


# --------------------------------------------------------------------------
# intra-block resolvers (exact, shape-generic over the block length)
# --------------------------------------------------------------------------

def _fixpoint_resolver(body, W):
    """Bounded parallel Jacobi over one block: re-book every event against
    the per-event W-vectors reconstructed from the previous pass, until the
    OBSERVED vectors converge (bitwise).  Convergence of the observed rows
    — not merely of the booking estimates — is the right exit test: a dead
    event's irrelevant worker pick may flap between passes without ever
    changing what any event observes, and conversely equal bookings under
    unequal observations would exit with stale outputs.  The returned
    ``(est, out)`` are always evaluated at the converged rows; ``p`` is
    the number of passes the block took, in ``[1, nev]`` (under ``vmap``
    each lane keeps its own count: the batched loop runs until the slowest
    lane converges and selects per lane)."""
    vbody = jax.vmap(body)

    def resolve(wf, ev):
        nev = jax.tree_util.tree_leaves(ev)[0].shape[0]

        def rows_of(est):
            return exclusive_running_max(booking_contrib(W, *est), wf)

        # pass 1 observes the carried vector alone (the empty-prefix rows)
        rows0 = jnp.broadcast_to(wf, (nev, W))
        est1, out1 = vbody(rows0, ev)

        def cond(c):
            p, rows, used = c[0], c[1], c[2]
            with stage("booking"):
                return jnp.any(rows != used) & (p < nev)

        def again(c):
            p, rows = c[0], c[1]
            est2, out2 = vbody(rows, ev)
            return p + 1, rows_of(est2), rows, est2, out2

        p, _, _, est, out = lax.while_loop(
            cond, again, (jnp.asarray(1), rows_of(est1), rows0, est1, out1))
        return est, out, p

    return resolve


def _unrolled_resolver(body, unroll=None):
    """Resolve one block as a fused straight-line sequential region; also
    returns the booking estimates so the caller can summarize the block."""
    def resolve(wf, ev):
        nev = jax.tree_util.tree_leaves(ev)[0].shape[0]

        def step(w, e):
            (widx, rel), out = body(w, e)
            return apply_bookings(w, widx, rel), ((widx, rel), out)

        _, (est, out) = lax.scan(
            step, wf, ev, unroll=nev if unroll is None else min(unroll, nev))
        return est, out, None

    return resolve


def _tree_concat(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.concatenate([x, y], axis=0), a, b)


def blocked_event_replay(body, wf0, events, *, block: int,
                         resolver: str = "fixpoint", unroll: int = 1,
                         scan: str = "seq", summary_backend: str = "xla",
                         interpret=None):
    """Replay a sorted event stream in blocks, carrying only the W-vector.

    ``body(wf, event) -> ((widx, rel), out)`` books one event against the
    worker free-at vector ``wf`` it observes: ``widx`` (M,) int are the
    booked workers (< 0 books nothing — the dead/padded convention),
    ``rel`` (M,) their release times (must be ``-inf`` wherever the event
    must not touch the pool), ``out`` an arbitrary output pytree.  Events
    is a pytree with leading axis N (the per-trial stream, already
    sorted).  ``block`` need not divide N: the ragged tail is resolved as
    one final partial block — no phantom events are ever synthesized.
    ``block=0`` picks the adaptive log-depth split (``ceil(n/3)``).

    ``block=1`` runs the plain sequential scan (bit-identical to the
    pre-blocking engines; ``unroll`` trims its per-step dispatch cost) —
    the oracle path.  For ``block > 1`` the intra-block resolver is:

    * ``"fixpoint"`` — the bounded parallel Jacobi described in the
      module docstring: exact in at most ``block`` passes, early-exit on
      convergence of the observed per-event W-vectors, all comparisons
      bitwise so the fixed point IS the sequential schedule.  Pass count
      tracks the longest intra-block dependency chain, so this is the
      depth-reduction mode: O(N/B·p) runtime steps, each (trials x
      B)-wide.  When bookings are placement-coupled (the raptor HA
      discipline: which worker is free decides the AZ-shared draws)
      chains approach the block length and the mode loses its edge —
      measured in EXPERIMENTS.md.
    * ``"unrolled"`` — resolve the block as one fused straight-line
      region (scan unrolling): events inside a block resolve sequentially
      in-register instead of iteratively in parallel.

    ``scan`` picks how resolved blocks chain across the stream:

    * ``"seq"`` — a ``lax.scan`` over blocks carries the W-vector:
      O(N/B) sequential depth.
    * ``"logdepth"`` — every block is summarized as a factored W x W
      max-plus operator (offset = the block's booking contributions) and
      ALL block entry vectors come out of one ``lax.associative_scan``
      over the summaries — O(log(N/B)) sequential depth per pass.  Entry
      vectors feed back into a block-level Jacobi iteration (every block
      re-resolves against its latest entry estimate, vmapped across
      blocks) whose fixed point is unique by the same strict
      lower-triangularity argument, now in block index: after pass ``p``
      blocks ``0..p`` are exact, so ``nb`` passes always suffice and the
      loop exits as soon as the entries stop changing.  The intra-block
      resolvers are reused unchanged; ``summary_backend`` routes the
      summary prefix scan ("xla" or the "pallas" VMEM kernel).

    Every (resolver, scan) configuration is bitwise-identical to the
    ``block=1`` oracle scan (tests/test_queue_properties.py).  Returns
    ``((wf_final, passes), outs)`` with each out leaf stacked along the
    event axis.  ``passes`` is the fixpoint resolver's pass count per
    block, (blocks,) int32 with a ragged tail's last, on the ``"seq"``
    chain; ``None`` on every other path.
    """
    W = int(wf0.shape[-1])
    n = int(jax.tree_util.tree_leaves(events)[0].shape[0])
    block = int(block)
    if not block:
        # adaptive split (the auto_config log-depth host default): two
        # Jacobi blocks + an equal ragged tail — ceil(n/3).  More blocks
        # multiply total work by the outer pass count (which is exactly
        # nb under bitwise choice coupling), fewer waste the tail's
        # single resolve; see EXPERIMENTS.md §log-depth.
        block = max(1, -(-n // 3))
    if scan not in ("seq", "logdepth"):
        raise ValueError(f"unknown block scan mode {scan!r}")

    if block <= 1 or (resolver == "unrolled" and scan == "seq"):
        def step(wf, ev):
            (widx, rel), out = body(wf, ev)
            return apply_bookings(wf, widx, rel), out
        wf_r, outs = lax.scan(step, wf0, events,
                              unroll=unroll if block <= 1 else block)
        return (wf_r, None), outs

    if resolver == "fixpoint":
        resolve = _fixpoint_resolver(body, W)
    elif resolver == "unrolled":
        # small blocks fuse into one straight-line region; big blocks cap
        # the codegen (compile cost grows with the unroll factor) and loop
        # a partially-unrolled scan instead — same schedule bitwise
        resolve = _unrolled_resolver(
            body, None if block <= 32 else max(unroll, 8))
    else:
        raise ValueError(f"unknown block resolver {resolver!r}")

    nb, rem = divmod(n, block)
    split = n - rem
    main = jax.tree_util.tree_map(
        lambda a: a[:split].reshape((nb, block) + a.shape[1:]), events)
    tail = (jax.tree_util.tree_map(lambda a: a[split:], events)
            if rem else None)

    def resolve_step(wf, ev):
        est, out, p = resolve(wf, ev)
        with stage("booking"):
            wf = jnp.maximum(wf, jnp.max(booking_contrib(W, *est), axis=0))
        return wf, (out, p)

    passes = None
    if scan == "seq":
        if nb:
            wf_r, (outs, passes) = lax.scan(resolve_step, wf0, main)
            outs = jax.tree_util.tree_map(
                lambda a: a.reshape((split,) + a.shape[2:]), outs)
        else:
            wf_r, outs = wf0, None
    else:
        if nb:
            wf_r, outs = _logdepth_replay(resolve, wf0, main, nb, W,
                                          summary_backend, interpret)
            outs = jax.tree_util.tree_map(
                lambda a: a.reshape((split,) + a.shape[2:]), outs)
        else:
            wf_r, outs = wf0, None
    if rem:
        wf_r, (out_t, p_t) = resolve_step(wf_r, tail)
        outs = out_t if outs is None else _tree_concat(outs, out_t)
        if scan == "seq":
            passes = (p_t[None] if passes is None
                      else jnp.concatenate([passes, p_t[None]]))
    return (wf_r, passes), outs


def _logdepth_replay(resolve, wf0, ev_blocks, nb, W, summary_backend,
                     interpret):
    """Block-level Jacobi over entry vectors with the associative max-plus
    prefix supplying every block's entry at O(log nb) depth per pass.

    Invariant at exit: the returned ``(est, out)`` were produced by a
    resolve pass whose entry estimates equal the entries those estimates
    regenerate — the unique fixed point, i.e. the sequential schedule.
    Summaries are offset-only (diag = 0): a block's effect on the carried
    vector is a pure elementwise max with its booking contributions, so
    the prefix scan composes float maxes only — exactly associative,
    keeping the whole mode bitwise against the sequential oracle.
    """
    vres = jax.vmap(resolve)
    zeros = jnp.zeros((nb, W), wf0.dtype)

    def prefix(est):
        off = block_summary(W, *est)            # (nb, W)
        return maxplus_prefix_entries(zeros, off, wf0,
                                      backend=summary_backend,
                                      interpret=interpret)

    entries0 = jnp.broadcast_to(wf0, (nb, W))
    est0, out0, _ = vres(entries0, ev_blocks)
    entries1, wf1 = prefix(est0)

    def cond(c):
        p, entries, used = c[0], c[1], c[2]
        return jnp.any(entries != used) & (p < nb)

    def again(c):
        p, entries = c[0], c[1]
        est, out, _ = vres(entries, ev_blocks)
        entries2, wf2 = prefix(est)
        return p + 1, entries2, entries, est, out, wf2

    _, _, _, est, out, wf_out = lax.while_loop(
        cond, again, (jnp.asarray(1), entries1, entries0, est0, out0, wf1))
    return wf_out, out


# --------------------------------------------------------------------------
# the shared booking step (task-FCFS stock discipline) + its blocked driver
# --------------------------------------------------------------------------

def bestfit_book_step(wf, ready, service):
    """Book one ready task: best-fit among free workers, earliest-free
    fallback when all are busy.

    Fused key (the PR-3 trick): free workers (``wf <= ready``) rank by
    ``wf`` — latest-freed-but-eligible wins, all keys >= 0 — busy workers
    by ``-wf`` (< 0, so they lose to any free worker, and among them
    ``argmax(-wf)`` IS the earliest-free fallback); ``-max(key)`` then
    equals the booking delay floor, so ``start = max(ready, -max(key))``
    needs no gather.  A ``ready`` of ``inf`` (unmaterialized / padding)
    books nothing: worker -1, start/fin inf.  Returns (worker, start, fin).
    """
    live = ~jnp.isinf(ready)
    key = jnp.where(wf <= ready, wf, -wf)
    w = jnp.argmax(key)
    start = jnp.maximum(ready, -jnp.max(key))
    fin = start + service
    return (jnp.where(live, w, -1), jnp.where(live, start, jnp.inf),
            jnp.where(live, fin, jnp.inf))


def blocked_bestfit_booking(wf0, ready, service, *, block: int,
                            full: bool = True, unroll: int = 16,
                            backend: str = "scan", interpret=None,
                            resolver: str = "fixpoint", scan: str = "seq",
                            summary_backend: str = "xla"):
    """Resolve one trial's whole ready-sorted stream of best-fit bookings.

    ``ready``/``service`` are (N,) (any N — a ragged tail resolves as one
    final partial block); ``wf0`` the (W,) entry free-at vector.  Returns
    ``(fin, start, worker)`` when ``full`` else ``(fin,)`` — the non-full
    form lets the stock fixed point over stage depth skip two (N,)-sized
    outputs per estimation pass.

    ``backend="scan"`` runs :func:`blocked_event_replay` (with its
    ``resolver``/``scan``/``summary_backend`` knobs passed through);
    ``"pallas"`` dispatches the fused intra-block kernel
    (:mod:`repro.kernels.queue_booking`), which keeps the whole block
    resolution in VMEM on accelerators (``interpret`` defaults per
    :func:`repro.kernels._compat.interpret_default`, so the same code path
    runs — and is CI-tested — on CPU).
    """
    if backend == "pallas":
        from repro.kernels.queue_booking.ops import book_stream
        fin, start, worker, _ = book_stream(
            ready[None], service[None], wf0[None], block=block,
            interpret=interpret)
        return (fin[0], start[0], worker[0]) if full else (fin[0],)
    if backend != "scan":
        raise ValueError(f"unknown booking backend {backend!r}")

    def body(wf, ev):
        w, start, fin = bestfit_book_step(wf, *ev)
        out = (fin, start, w) if full else (fin,)
        # widx=-1 already gates dead events out of the pool; fin is their
        # (constant) inf, so the convergence check stays stable
        return (w[None], fin[None]), out

    _, outs = blocked_event_replay(body, wf0, (ready, service),
                                   block=block, unroll=unroll,
                                   resolver=resolver, scan=scan,
                                   summary_backend=summary_backend,
                                   interpret=interpret)
    return outs


def blocked_sorted_booking(wf0, ready, service, *, block: int):
    """Finish times of a ready-sorted best-fit booking stream, resolved
    block-parallel through the order-statistic form of the recurrence.

    Under ready-sorted FCFS the booked *worker* is interchangeable (any
    policy that books a free worker when one exists and the earliest-free
    otherwise leaves the same multiset of future-relevant free-at times —
    EXPERIMENTS.md), so only the sorted pool matters and the start time
    collapses to an order statistic:

        st_i = max(r_i, c_i-th smallest of (pool_in ∪ {fin_j : j < i}))

    with ``c_i`` the count of live events through ``i``.  That dependency
    is strictly lower-triangular in ``fin``, so the same bounded Jacobi
    fixed point applies — but errors now propagate only along *same-worker
    chains* (a fin estimate that keeps its rank perturbs nothing), so the
    pass count stays near (block bookings)/W even at high utilisation,
    where the worker-identity Jacobi of :func:`blocked_event_replay`
    degrades toward one event per pass.  The cost: worker ids are never
    materialized — this is the measurement path; the trace path resolves
    ids through the generic fixed point instead.

    Each pass is one sort of the (W + B) pool tagged by availability rank
    plus a cumulative-count selection — the "chunked max-plus scan" of the
    blocked substrate.  Returns ``(fin,)`` shaped like ``ready`` (inf for
    dead events); bitwise equal to the sequential scan's finish times.
    """
    W = int(wf0.shape[-1])
    n = int(ready.shape[0])
    block = int(block)

    def resolver_at(blk):
        idx = jnp.arange(blk)
        avail = jnp.concatenate([jnp.zeros(W, jnp.int32),
                                 1 + idx.astype(jnp.int32)])

        def resolve(pool, ev):
            r, s = ev
            live = ~jnp.isinf(r)
            c = jnp.cumsum(live)        # live bookings through event i

            def one_pass(fin):
                vals = jnp.concatenate([pool, fin])
                order = jnp.argsort(vals)
                v_s, a_s = vals[order], avail[order]
                # element q is in event i's pool iff its availability rank
                # a_s[q] <= i (0 = entry pool, j+1 = fin_j); the c_i-th
                # included element of the sorted tape IS the order statistic
                incl = a_s[None, :] <= idx[:, None]
                cnt = jnp.cumsum(incl, axis=1)
                hit = incl & (cnt == c[:, None])
                sig = jnp.sum(jnp.where(hit, v_s, 0.0), axis=1)
                st = jnp.maximum(r, sig)
                return jnp.where(live, st + s, jnp.inf)

            fin0 = jnp.where(live, r + s, jnp.inf)  # zero-queueing bound
            fin1 = one_pass(fin0)

            def cond(carry):
                p, fin, prev = carry
                return jnp.any(fin != prev) & (p < blk)

            def again(carry):
                p, fin, _ = carry
                return p + 1, one_pass(fin), fin

            _, fin, _ = lax.while_loop(cond, again,
                                       (jnp.asarray(1), fin1, fin0))
            # block exit: the c_B consumed values are exactly the c_B
            # smallest of the pool ∪ fins (consume-min equivalence);
            # keep the rest
            tape = jnp.sort(jnp.concatenate([pool, fin]))
            return lax.dynamic_slice(tape, (c[-1],), (W,)), fin

        return resolve

    # ragged tail: the remainder resolves as one final partial block
    # against the carried pool — never via phantom events
    nb, rem = divmod(n, block)
    split = n - rem
    pool = jnp.sort(wf0)
    if nb:
        pool, fin = lax.scan(
            resolver_at(block), pool,
            jax.tree_util.tree_map(lambda a: a[:split].reshape(nb, block),
                                   (ready, service)))
        fin = fin.reshape(split)
    else:
        fin = jnp.zeros((0,), ready.dtype)
    if rem:
        _, fin_t = resolver_at(rem)(pool, (ready[split:], service[split:]))
        fin = jnp.concatenate([fin, fin_t])
    return (fin,)


def stock_booking_fins(wf0, ready, service, *, block: int,
                       backend: str = "scan", interpret=None,
                       scan: str = "seq", summary_backend: str = "xla"):
    """Finish times only — the form the stock stage-depth fixed point
    consumes on every estimation pass.  Dispatch: ``block <= 1`` runs the
    sequential oracle scan, larger blocks the order-statistic resolver
    (``scan="seq"``) or the log-depth generic replay (``scan="logdepth"``),
    ``backend="pallas"`` the fused VMEM kernel."""
    if backend == "pallas" or block <= 1:
        return blocked_bestfit_booking(
            wf0, ready, service, block=max(block, 1), full=False,
            backend=backend, interpret=interpret)
    if scan == "logdepth":
        return blocked_bestfit_booking(
            wf0, ready, service, block=block, full=False, backend=backend,
            resolver="unrolled", scan="logdepth",
            summary_backend=summary_backend, interpret=interpret)
    return blocked_sorted_booking(wf0, ready, service, block=block)
