"""Where the sim-side Pallas kernels run compiled and where interpreted."""
from __future__ import annotations


def interpret_default() -> bool:
    """Whether Pallas calls should default to interpret mode here.

    Compiled on the TPU; interpreted on the CPU, where tests and CI run
    the same kernels through the Pallas interpreter.  Any other backend
    is an error: silently interpreting on an accelerator would measure
    the interpreter, not the kernel.  Ops with an ``interpret=None`` knob
    resolve it through this one gate.
    """
    import jax
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"the Pallas kernels compile for the TPU only; "
                       f"backend {backend!r} would run them interpreted")
