"""spmv_ms.x4: window seconds over the applies completed (four-chip cell)."""
from bench.readers import spmv_ms as read  # noqa: F401
