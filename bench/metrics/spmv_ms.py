"""spmv_ms: window seconds over the applies completed (one-chip cells)."""
from bench.readers import spmv_ms as read  # noqa: F401
