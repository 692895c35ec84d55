"""host_ms.spmv: the executor host path per apply (one-chip cells)."""
from bench.readers import host_ms as read  # noqa: F401
