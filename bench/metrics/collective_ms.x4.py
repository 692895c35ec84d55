"""collective_ms.x4: all-to-all device time per apply, busiest chip (four-chip
cell)."""
from bench.readers import collective_ms as read  # noqa: F401
