"""Compute kernels for the stencil update.

Two kernel languages (the reference's Plain/KernelAbstractions pair,
``Inputs.jl:110-120``, re-imagined for TPU):

* ``"xla"``    — jnp/lax ops, fused by the XLA compiler (default; legacy
  config values "Plain" and "KernelAbstractions" alias here).
* ``"pallas"`` — hand-fused Pallas TPU kernel (``kernel_language = "Pallas"``).

The two languages have *different* call contracts (deliberately — the
Pallas kernel's whole advantage is consuming interior arrays + halo faces
with in-kernel RNG, while the XLA kernel consumes ghost-padded arrays +
a pre-generated noise field), so there is no uniform kernel callable:
the chain schedules (``parallel/schedule.py``) have one round function
per language.
``validate_kernel_language`` front-loads the import/availability check so
a bad config fails at construction, not at first ``iterate`` (the
reference defers dispatch errors to runtime fallbacks,
``public.jl:31-32, 77-78``).
"""

from __future__ import annotations

from . import stencil  # noqa: F401 — re-exported compute core


def validate_kernel_language(lang: str) -> None:
    """Raise if ``lang`` is unknown or its kernel module cannot load."""
    if lang == "xla":
        return
    if lang in ("pallas", "auto"):
        # "auto" may resolve to the Pallas path, so its kernel module
        # must load too — a broken install fails at construction either
        # way, not at dispatch.
        from . import pallas_stencil  # noqa: F401 — import is the check

        return
    raise ValueError(f"Unknown kernel language: {lang!r}")
