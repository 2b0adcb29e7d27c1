"""Which way a Pallas entry point runs when the caller leaves ``interpret``
unset: compiled by Mosaic on the TPU backend, interpreted on a CPU backend
that was asked for, and an error anywhere else."""

from __future__ import annotations

import jax


def interpret_default() -> bool:
    """Resolve ``interpret=None`` for the kernels in this package.

    Interpret mode exists so the kernels' control flow is unit-testable on
    the CPU. It is reached by an explicit ``interpret=True`` or by the CPU
    backend having been asked for (``JAX_PLATFORMS`` / ``jax_platforms``
    names ``cpu`` first) — never as the outcome of a TPU that failed to
    initialize: a process that wanted the chip and landed elsewhere raises
    here instead of running the kernels interpreted and passing."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    requested = (jax.config.jax_platforms or "").split(",")[0].strip()
    if backend == "cpu" and requested == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernel dispatched on backend {backend!r} with "
        f"jax_platforms={jax.config.jax_platforms!r}: the kernels compile "
        "on TPU and interpret only on a CPU backend that was asked for "
        "(JAX_PLATFORMS=cpu) or with an explicit interpret=True")
