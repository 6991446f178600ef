"""Backend detection — the analog of the reference's extension-availability
probing (reference: apex/parallel/__init__.py:13-19, apex/amp/scaler.py:66-80):
every fused op here has a Pallas fast path and a pure-XLA fallback, chosen
at trace time.

Detection is stateless and keyed on the *current* default JAX backend.  A
mid-process backend switch is picked up as soon as JAX itself re-resolves
the backend — i.e. after ``jax.extend.backend.clear_backends()`` +
``jax.config.update("jax_platforms", ...)``, which is exactly what
``__graft_entry__._force_cpu_platform`` performs (a bare config update
without clearing leaves JAX's own backend cache, and therefore this module,
on the old platform).  The env override ``APEX_TPU_DISABLE_PALLAS`` is
honored per call.
"""

from __future__ import annotations

import os

__all__ = ["is_tpu", "supports_pallas", "default_implementation"]

_TPU_PLATFORMS = ("tpu",)


def _current_platform() -> str:
    """The default backend's platform.  A backend that fails to start
    raises here: answering anything else would send every kernel to
    interpret mode or XLA on a machine whose chip did not come up."""
    import jax

    # cached inside JAX; re-resolves once clear_backends() has run
    return jax.default_backend().lower()


def is_tpu() -> bool:
    return _current_platform() in _TPU_PLATFORMS


def supports_pallas() -> bool:
    """Whether Pallas TPU kernels can compile on the current backend."""
    if os.environ.get("APEX_TPU_DISABLE_PALLAS"):
        return False
    return is_tpu()


def default_implementation() -> str:
    return "pallas" if supports_pallas() else "xla"
