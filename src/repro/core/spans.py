"""Host spans on the profiler's clock.

``span(name)`` marks a stretch of host work as a
``jax.profiler.TraceAnnotation``, so that it lands in a profiler trace on
the same clock as the device's operations.  With no profiler running an
annotation is a flag test.  Every span name starts ``repro.``; the shard
programs name their device work with ``jax.named_scope`` under the same
prefix (see :mod:`repro.core.spmv_jax`).

This module never imports jax: before jax is loaded ``span`` is a null
context, so the simulate backend and the numpy solvers stay usable on a
jax-free install.
"""
from __future__ import annotations

import contextlib
import sys

__all__ = ["span"]


def span(name: str):
    """A profiler annotation named ``name``, or a null context while jax
    is not loaded."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)
