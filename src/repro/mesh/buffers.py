"""Persistent device-buffer registry + global mesh placement helpers.

Two jobs, one seam:

* :class:`BufferRegistry` / :class:`BufferNamespace` — an alpa-style
  persistent buffer store (named CSR/value/plan arrays pinned on device
  across solves, explicit lifecycle + eviction stats).  A namespace
  speaks the dict protocol so it plugs straight into a compiled plan's
  ``_dev_cache`` (:func:`repro.core.spmv_jax._memo_device_arrays`): the
  first bind stages each host array once, every later bind — and every
  hot value swap — reuses the resident device buffer.  Evicting a plan
  (``serve.PlanCache`` LRU / elastic ``rebuild``) releases its namespace
  so the device memory is accounted, not leaked.

* Placement — every mesh-shaped array is staged as a ``jax.Array`` laid
  out ``P("node", "proc")`` over the topology's device mesh, one shard
  per device: on one chip, on the four chips of a host, or as a GLOBAL
  array over a multi-process ``jax.distributed`` mesh, where each process
  materialises only its addressable shards.  ``fetch_mesh_array``
  inverts it: fully-addressable results fetch with ``np.asarray``,
  global results gather their shards across processes (mask-select per
  owner, so the round trip is bitwise exact — no zero+sum, which can
  turn ``-0.0`` into ``+0.0``).

Importing this module never touches jax — everything jax lives behind
function calls (the simulate backend stays usable on a jax-free numpy
install).
"""
from __future__ import annotations

import weakref
from typing import Dict, Optional

import numpy as np

from repro.core.topology import Topology

__all__ = ["BufferNamespace", "BufferRegistry", "default_registry",
           "process_count", "is_multiprocess", "mesh_for",
           "stage_mesh_array", "input_stager", "fetch_mesh_array"]


# ---------------------------------------------------------------------------
# Placement: single-process vs jax.distributed global arrays
# ---------------------------------------------------------------------------

def process_count() -> int:
    """Processes in the jax.distributed job (1 when unattached/jax-free).

    Only a missing jax counts as one process: a backend that fails to
    start raises here instead of passing for a single CPU process.
    """
    try:
        import jax
    except ImportError:
        return 1
    return int(jax.process_count())


def is_multiprocess() -> bool:
    return process_count() > 1


_MESH_CACHE: Dict[tuple, object] = {}


def mesh_for(topo: Topology):
    """The shared ``(node, proc)`` device mesh for a topology, memoized —
    every executor/stager bound to the same layout reuses one mesh object
    (jax caches sharding/layout decisions per mesh instance)."""
    key = (topo.n_nodes, topo.ppn)
    if key not in _MESH_CACHE:
        from repro.compat import make_mesh
        _MESH_CACHE[key] = make_mesh((topo.n_nodes, topo.ppn),
                                     ("node", "proc"))
    return _MESH_CACHE[key]


def _global_sharding(topo: Topology):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh_for(topo), P("node", "proc"))


def stage_mesh_array(g: np.ndarray, topo: Topology, dtype=None):
    """Device-stage one mesh-shaped ``[n_nodes, ppn, ...]`` host array.

    Returns a ``jax.Array`` sharded ``P("node", "proc")`` over
    :func:`mesh_for` ``(topo)``: shard ``[i, j]`` lives on mesh device
    ``(i, j)``, so a four-chip topology holds one shard per chip.  Under
    a multi-process mesh each process materialises only the shards it
    can address (its own node rows), never the full job's buffers.
    """
    import jax
    g = np.asarray(g, dtype)
    return jax.make_array_from_callback(g.shape, _global_sharding(topo),
                                        lambda idx: g[idx])


def input_stager(topo: Topology):
    """Per-call operand stager for the jitted run path:
    ``stage(shards, dtype=f32)`` places the packed ``[n_nodes, ppn,
    pad(, nv)]`` operand on the mesh (:func:`stage_mesh_array`) so the
    shard_map program consumes it where it lies."""

    def stage(shards, dtype=np.float32):
        return stage_mesh_array(np.asarray(shards, dtype), topo)

    return stage


def fetch_mesh_array(w) -> np.ndarray:
    """Host copy of a (possibly global) device array, bitwise exact.

    Fully-addressable arrays (every single-process result) go through
    ``np.asarray`` — the seed path.  Global arrays fill the local shards,
    ``process_allgather`` the per-process views, and mask-select each
    element from its owning process.
    """
    if getattr(w, "is_fully_addressable", True):
        return np.asarray(w)
    from jax.experimental import multihost_utils
    shards = list(w.addressable_shards)
    full = np.zeros(w.shape, np.asarray(shards[0].data).dtype)
    have = np.zeros(w.shape, bool)
    for s in shards:
        full[s.index] = np.asarray(s.data)
        have[s.index] = True
    all_vals = np.asarray(multihost_utils.process_allgather(full))
    all_have = np.asarray(multihost_utils.process_allgather(have))
    out = full
    for p in range(all_vals.shape[0]):
        out = np.where(all_have[p], all_vals[p], out)
    return np.asarray(out, full.dtype)


# ---------------------------------------------------------------------------
# The persistent buffer registry
# ---------------------------------------------------------------------------

class BufferNamespace:
    """One plan's named device buffers (dict protocol; a ``_dev_cache``).

    Lifecycle: arrays enter via ``__setitem__`` (counted as ``staged``),
    are read back by every executor bind via ``__getitem__`` (``reused``),
    leave individually via ``pop`` (hot value swaps retire exactly the
    swapped names) or wholesale via ``release()`` (plan eviction /
    elastic rebuild).  Byte counts use the logical array size — the
    registry's ``resident_bytes`` is the job-wide figure, not per-host.
    """

    def __init__(self, registry: "BufferRegistry", label: str):
        self._registry = registry
        self.label = label
        self._bufs: Dict[str, object] = {}
        self._nbytes: Dict[str, int] = {}
        self.released = False

    def __contains__(self, name: str) -> bool:
        return name in self._bufs

    def __getitem__(self, name: str):
        self._registry.stats["reused"] += 1
        return self._bufs[name]

    def __setitem__(self, name: str, arr) -> None:
        if name in self._bufs:
            self.pop(name)
        nb = int(getattr(arr, "nbytes", 0))
        self._bufs[name] = arr
        self._nbytes[name] = nb
        st = self._registry.stats
        st["staged"] += 1
        st["staged_bytes"] += nb

    def pop(self, name: str, default=None):
        if name not in self._bufs:
            return default
        arr = self._bufs.pop(name)
        nb = self._nbytes.pop(name)
        st = self._registry.stats
        st["evicted"] += 1
        st["evicted_bytes"] += nb
        return arr

    def __len__(self) -> int:
        return len(self._bufs)

    def keys(self):
        return self._bufs.keys()

    def resident_bytes(self) -> int:
        return sum(self._nbytes.values())

    def release(self) -> int:
        """Drop every buffer in the namespace; returns bytes released.
        Idempotent — the serve cache may release through several paths."""
        nb = self.resident_bytes()
        for name in list(self._bufs):
            self.pop(name)
        if not self.released:
            self.released = True
            self._registry.stats["namespaces_released"] += 1
        return nb


class BufferRegistry:
    """Job-wide accounting over every live :class:`BufferNamespace`.

    The registry never holds strong references to buffers — namespaces
    own them, the registry tracks them weakly, so a garbage-collected
    plan frees its device memory exactly as before the registry existed.
    """

    def __init__(self, name: str = "default"):
        self.name = name
        self._namespaces: "weakref.WeakSet[BufferNamespace]" = weakref.WeakSet()
        self.stats: Dict[str, int] = {
            "staged": 0, "staged_bytes": 0,
            "reused": 0,
            "evicted": 0, "evicted_bytes": 0,
            "namespaces_created": 0, "namespaces_released": 0,
        }

    def namespace(self, label: str = "plan") -> BufferNamespace:
        ns = BufferNamespace(self, label)
        self._namespaces.add(ns)
        self.stats["namespaces_created"] += 1
        return ns

    def live_namespaces(self) -> int:
        return sum(1 for ns in self._namespaces if not ns.released)

    def resident_bytes(self) -> int:
        return sum(ns.resident_bytes() for ns in self._namespaces)

    def report(self) -> Dict[str, object]:
        return dict(self.stats, name=self.name,
                    live_namespaces=self.live_namespaces(),
                    resident_bytes=self.resident_bytes())


_DEFAULT: Optional[BufferRegistry] = None


def default_registry() -> BufferRegistry:
    """The process-wide registry every compiled plan's ``_dev_cache``
    hangs off (tests may construct private registries)."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = BufferRegistry()
    return _DEFAULT
