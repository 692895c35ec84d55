"""spmv_p95_ms.x4: 95th percentile of the apply walls (four-chip cell)."""
from bench.readers import spmv_p95_ms as read  # noqa: F401
