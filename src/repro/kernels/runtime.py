"""Kernel runtime knobs shared by every Pallas wrapper in kernels/*.

One rule decides whether Pallas kernels run in interpret mode (the
Mosaic interpreter, required off-TPU) or compiled (`interpret=False`, the
real-TPU path).  Every wrapper defaults to ``interpret=None`` and
resolves here:

* an explicit ``interpret=`` argument wins (tests pin it);
* else interpret is ON exactly when the default JAX backend is not a TPU.

There is no process-level override: on a TPU backend an unpinned kernel
always compiles, so nothing can silently fall back to the interpreter
on the chip.  Resolution happens at trace time (the flag is a static jit
argument), so an unpinned wrapper resolves once per traced shape.
"""
from __future__ import annotations


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Resolve the effective interpret flag for a Pallas call."""
    if interpret is not None:
        return bool(interpret)
    import jax
    return jax.default_backend() != "tpu"
