"""Jitted wrapper for the fused queue-booking kernel.

``interpret=None`` resolves through ``kernels._compat.interpret_default``
(compiled on TPU, the Pallas interpreter on CPU) so the same call site —
including ``QueueFlightSim(booking_backend="pallas")`` — runs on CPU CI
and on the chip unchanged.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels._compat import interpret_default
from repro.kernels.queue_booking.kernel import queue_booking
from repro.kernels.queue_booking.ref import book_stream_ref  # noqa: F401

LANES = 128


@partial(jax.jit, static_argnames=("block", "interpret"))
def _book_stream(ready, service, wf0, *, block, interpret):
    return queue_booking(ready, service, wf0, block=block,
                         interpret=interpret)


def book_stream(ready, service, wf0, *, block: int = 64, interpret=None):
    """Resolve batched ready-sorted booking streams on the kernel.

    ready/service: (T, N); wf0: (T, W).  ``block`` is rounded up to a
    multiple of 128 lanes, the tile Mosaic accepts; the booking runs
    sequentially over the whole stream either way, so the tile changes
    only how much of it sits in VMEM per grid step, never the schedule.
    N is padded up to a multiple of the tile with dead events (ready=inf,
    service=0) and the padding is sliced back off.  Returns
    (fin, start, worker, wf_final).
    """
    if interpret is None:
        interpret = interpret_default()
    tile = -(-max(int(block), 1) // LANES) * LANES
    T, n = ready.shape
    npad = -(-n // tile) * tile
    if npad > n:
        pad = npad - n
        ready = jnp.concatenate(
            [ready, jnp.full((T, pad), jnp.inf, ready.dtype)], axis=1)
        service = jnp.concatenate(
            [service, jnp.zeros((T, pad), service.dtype)], axis=1)
    fin, st, wk, wf = _book_stream(ready, service, wf0, block=tile,
                                   interpret=bool(interpret))
    return fin[:, :n], st[:, :n], wk[:, :n], wf
