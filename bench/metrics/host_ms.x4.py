"""host_ms.x4: the executor host path per apply (four-chip cell)."""
from bench.readers import host_ms as read  # noqa: F401
