"""What the metric readers under ``bench/metrics/`` compute, shared by
the readers of one quantity in cells that report different end-to-end
metrics (``host_ms.spmv`` in the one-chip cells, ``host_ms.x4`` in the
four-chip cell).  Each returns None where the run holds nothing to read.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from bench.roofline import share_pct


def spmv_ms(run) -> Optional[float]:
    """Window seconds over the applies completed in it (host clock, host
    numpy in to host numpy out)."""
    w = run.window
    if w.counts != "apply" or not w.completed:
        return None
    return w.seconds / w.completed * 1e3


def spmv_p95_ms(run) -> Optional[float]:
    """95th percentile of every apply's wall in the window (host clock;
    numpy's linear interpolation between order statistics)."""
    lat = run.window.latencies
    if run.window.counts != "apply" or not lat:
        return None
    return float(np.percentile(lat, 95)) * 1e3


def host_ms(run) -> Optional[float]:
    """Per apply, its wall minus the time in which any of the cell's chips
    ran an operation inside it (device trace, same clock): the executor's
    host path (pack, stage, dispatch, fetch, unpack)."""
    t = run.trace
    if t is None or run.window.counts != "apply":
        return None
    host = t.span_host_s.get("bench.apply")
    if not host:
        return None
    return sum(host) / len(host) * 1e3


def device_idle_pct(run, counts: str) -> Optional[float]:
    """1 - busy / traced window, in %, averaged over the cell's chips
    (device trace), in a window that counts ``counts``."""
    t = run.trace
    if t is None or run.window.counts != counts or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_mean_s() / t.window_s)


def spmv_roofline(run) -> Optional[float]:
    """The whole apply's share of its HBM roofline, in %: every op of the
    shard program (packing, the exchange, the local product) inside the
    applies, lowest chip."""
    t = run.trace
    if t is None or run.window.counts != "apply":
        return None
    return share_pct(run, t.span_busy_dev.get("bench.apply", {}))


def ell_spmv_roofline(run) -> Optional[float]:
    """The local product's share of its HBM roofline, in %: the time of
    ``while`` ops inside the applies, lowest chip.  The ELL slot loop
    (``kernels/ell_spmv``, a loop over the kmax slots) is the shard
    program's only loop; a product that runs no loop leaves it out."""
    t = run.trace
    if t is None or run.window.counts != "apply":
        return None
    return share_pct(run, t.span_loop_dev.get("bench.apply", {}))


def collective_ms(run) -> Optional[float]:
    """Per apply, the device time of the exchange's all-to-all ops (HLO
    opcode ``all-to-all``) on the busiest chip; None where no chip ran
    one."""
    t = run.trace
    if t is None or run.window.counts != "apply":
        return None
    n = len(t.span_walls.get("bench.apply", ()))
    per_dev = t.span_collective_dev.get("bench.apply", {})
    if not n or not per_dev or max(per_dev.values()) <= 0.0:
        return None
    return max(per_dev.values()) / n * 1e3
