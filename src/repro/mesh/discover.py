"""Topology autodiscovery: derive ``Topology`` from the live jax mesh.

The paper's premise is exploiting the *actual* node-processor layout;
the seed declared it by hand everywhere.  Discovery reads the runtime
instead:

* ``n_nodes``  = ``jax.process_count()`` — one "node" per jax process
  (each process addresses its own devices; crossing processes is the
  expensive hop, exactly the paper's node boundary).
* ``ppn``      = ``jax.local_device_count()`` — devices this process
  addresses.

Rules:

* jax-free install (simulate backend only) → ``Topology(1, 1)``, the
  seed default.
* single process → ``Topology(1, local_device_count)``; with one device
  that is ``Topology(1, 1)`` — bit-identical to the declared default.
* multi-process (after :func:`repro.mesh.launcher.attach`) →
  ``Topology(process_count, local_device_count)``.  The device layout
  must be uniform (``device_count == process_count * local_device_count``)
  because the SMP rank order assumes equal ppn — a ragged job raises
  :class:`DiscoveryError` rather than silently mislaying ranks.
"""
from __future__ import annotations

from typing import Dict

from repro.core.topology import Topology

__all__ = ["DiscoveryError", "discover_topology", "discovery_report"]


class DiscoveryError(RuntimeError):
    """The live device layout cannot be expressed as Topology(n, ppn)."""


def discover_topology(*, strict: bool = True) -> Topology:
    """The ``Topology`` of the running job (see module docstring).

    ``strict=False`` skips the uniform-layout check and trusts the local
    counts (useful when probing a partially-initialised job).
    """
    try:
        import jax
    except ImportError:      # jax-free numpy install: the seed default
        return Topology(n_nodes=1, ppn=1)
    n_proc = int(jax.process_count())
    ppn = int(jax.local_device_count())
    if strict:
        total = int(jax.device_count())
        if total != n_proc * ppn:
            raise DiscoveryError(
                f"non-uniform device layout: {total} global devices across "
                f"{n_proc} processes with {ppn} local — Topology(n_nodes, "
                f"ppn) needs every process to address the same device count")
    return Topology(n_nodes=n_proc, ppn=ppn)


def discovery_report() -> Dict[str, object]:
    """Machine-readable view of what discovery saw (benchmarks embed it)."""
    try:
        import jax
    except ImportError:
        return {"source": "fallback", "jax": False,
                "n_nodes": 1, "ppn": 1, "platform": "none"}
    topo = discover_topology(strict=False)
    return {
        "source": "jax",
        "jax": True,
        "n_nodes": topo.n_nodes,
        "ppn": topo.ppn,
        "process_index": int(jax.process_index()),
        "device_count": int(jax.device_count()),
        "platform": str(jax.devices()[0].platform),
    }
