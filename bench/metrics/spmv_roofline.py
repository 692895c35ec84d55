"""spmv_roofline: the whole apply's share of its HBM roofline (one-chip
cells)."""
from bench.readers import spmv_roofline as read  # noqa: F401
