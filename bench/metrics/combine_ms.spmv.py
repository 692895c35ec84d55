"""combine_ms.spmv: per apply, the device time inside the applies that lies
outside the local product's slot loop, on the busiest chip, in ms (one-chip
apply cells whose local product splits rows).

With a row-split ELL that is the combine of the chunk partials
(``repro.local.combine``) plus the few ops around the loop; where no loop
runs (a COO product) it is the whole product.  A chip with no ``while``
op counts its loop time as 0.  Self-contained: reads only the trace
summary the harness hands every reader."""
from __future__ import annotations

from typing import Optional


def read(run) -> Optional[float]:
    t = run.trace
    if t is None or run.window.counts != "apply":
        return None
    n = len(t.span_walls.get("bench.apply", ()))
    busy = t.span_busy_dev.get("bench.apply", {})
    if not n or not busy:
        return None
    loop = t.span_loop_dev.get("bench.apply", {})
    return max(s - loop.get(d, 0.0) for d, s in busy.items()) / n * 1e3
