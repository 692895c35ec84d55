"""Platform policies for the installed jax (0.9).

Call sites use the jax 0.9 API directly (``jax.shard_map``,
``jax.lax.axis_size``, ``jax.set_mesh``, ``pltpu.CompilerParams``,
dict-valued ``Compiled.cost_analysis()``).  Three policies stay here:

* :func:`make_mesh` — ``jax.make_mesh`` defaults to Explicit axes; every
  program in this repo runs manual shard_map collectives over Auto axes.
* :func:`interpret_mode` — Pallas kernels run in interpret mode only when
  the default backend is the CPU; on a TPU they always compile.
* :func:`use_compile_cache` — where entry points keep jax's persistent
  compilation cache.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import jax


def make_mesh(axis_shapes: Sequence[int],
              axis_names: Sequence[str]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel's ``interpret`` argument: an explicit bool wins
    (compile rehearsals pass ``False`` on a CPU host); ``None`` follows
    the platform — interpret only on the CPU backend."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"


def use_compile_cache(root: str) -> str:
    """Keep jax's persistent compilation cache under ``<root>/.jax_cache``.

    ``root`` is the checkout, derived by the caller from its own file
    location, so every run of that checkout hits the same directory.
    When ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    nothing is set here.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
