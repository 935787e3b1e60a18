"""JAX-native Raptor combinators: the state-sharing stream and preemption
semantics expressed as collective dataflow over a *flight axis* of the mesh.

On a real fleet each flight member is a separate executor group (pod or DP
slice) with its own latency/failure behaviour; these combinators express the
adopt-first-output rule so the same program runs under pjit on any mesh:

- ``first_finisher``      : every member contributes (value, latency); all
                            members adopt the min-latency member's value.
                            == the state-sharing broadcast + preemption.
- ``k_of_n_mean``         : mean over the k earliest/healthy members
                            (straggler-dropping gradient aggregation).
- ``masked_mean``         : mean over members with health=1; degrades
                            gracefully exactly like a reduced flight
                            (paper §3.3.2) and fails only if all fail (p^N).

All functions are written for use inside ``shard_map`` with a named axis.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

P = jax.sharding.PartitionSpec


def _axis_rank(axis_name: str):
    return jax.lax.axis_index(axis_name)


def first_finisher(value, latency, axis_name: str):
    """Adopt the value of the member with the smallest latency.

    value: any pytree (same structure on every member); latency: scalar.
    Returns (winner_value, winner_index).  Cost: one all-gather of the
    scalar latencies + one psum of the value bytes.
    """
    lats = jax.lax.all_gather(latency, axis_name)           # [F]
    winner = jnp.argmin(lats)
    me = _axis_rank(axis_name)
    is_winner = (me == winner).astype(jnp.float32)

    def pick(v):
        contrib = v.astype(jnp.float32) * is_winner
        return jax.lax.psum(contrib, axis_name).astype(v.dtype)

    return jax.tree.map(pick, value), winner


def masked_mean(value, healthy, axis_name: str):
    """Mean over healthy members; returns (mean, n_healthy).

    healthy: scalar {0,1}.  If all members are unhealthy the result is 0 and
    n_healthy==0 — callers treat that as job failure (prob p^N).
    """
    h = healthy.astype(jnp.float32)
    n = jax.lax.psum(h, axis_name)
    denom = jnp.maximum(n, 1.0)

    def agg(v):
        return (jax.lax.psum(v.astype(jnp.float32) * h, axis_name)
                / denom).astype(v.dtype)

    return jax.tree.map(agg, value), n


def k_of_n_mean(value, latency, k: int, axis_name: str):
    """Mean over the k members with the smallest latency (drop stragglers).

    Deterministic tie-break by member index.
    """
    lats = jax.lax.all_gather(latency, axis_name)           # [F]
    f = lats.shape[0]
    order = jnp.argsort(lats)
    me = _axis_rank(axis_name)
    my_rank = jnp.nonzero(order == me, size=1)[0][0]
    keep = (my_rank < k).astype(jnp.float32)

    def agg(v):
        return (jax.lax.psum(v.astype(jnp.float32) * keep, axis_name)
                / float(k)).astype(v.dtype)

    return jax.tree.map(agg, value)


# --------------------------------------------------------------------------
# mesh-level wrappers
# --------------------------------------------------------------------------

def speculative_apply(fn, mesh, flight_axis: str, value_spec, *,
                      latency_fn=None):
    """Run ``fn(member_index, *args) -> (value, latency)`` on every member of
    the flight axis and adopt the first finisher's value everywhere.

    ``value_spec``: out PartitionSpec *inside* a member (without the flight
    axis).  Returns a function over global arrays.
    """
    def member_fn(*args):
        idx = jax.lax.axis_index(flight_axis)
        value, latency = fn(idx, *args)
        adopted, winner = first_finisher(value, latency, flight_axis)
        return adopted, winner

    def wrapped(*args):
        return jax.shard_map(
            member_fn, mesh=mesh,
            in_specs=tuple(P() for _ in args),
            out_specs=(value_spec, P()), check_vma=False,
        )(*args)

    return wrapped
