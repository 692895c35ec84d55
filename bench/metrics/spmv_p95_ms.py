"""spmv_p95_ms: 95th percentile of the apply walls (one-chip cells)."""
from bench.readers import spmv_p95_ms as read  # noqa: F401
