"""Jitted wrapper for the max-plus summary-scan kernel.

``interpret=None`` resolves through ``kernels._compat.interpret_default``
(compiled on TPU, the Pallas interpreter on CPU) so the same call site —
including ``QueueFlightSim(summary_backend="pallas")`` via
``scan_core.maxplus_prefix_entries`` — runs on CPU CI and on the chip
unchanged.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels._compat import interpret_default
from repro.kernels.maxplus_scan.kernel import maxplus_scan
from repro.kernels.maxplus_scan.ref import maxplus_scan_ref  # noqa: F401


@partial(jax.jit, static_argnames=("interpret",))
def _maxplus_entries(diag, off, wf0, *, interpret):
    return maxplus_scan(diag, off, wf0, interpret=interpret)


def maxplus_entries(diag, off, wf0, interpret=None):
    """Batched factored-operator prefix: diag/off (T, nb, W), wf0 (T, W).

    Returns ``(entries (T, nb, W), wf_out (T, W))`` — see
    :func:`repro.sim.scan_core.maxplus_prefix_entries` for the contract.
    """
    if interpret is None:
        interpret = interpret_default()
    return _maxplus_entries(diag, off, wf0, interpret=bool(interpret))
