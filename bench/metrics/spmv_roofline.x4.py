"""spmv_roofline.x4: the whole apply's share of its HBM roofline, lowest chip
(four-chip cell)."""
from bench.readers import spmv_roofline as read  # noqa: F401
