"""ell_spmv_roofline.x4: the ELL slot loop's share of its HBM roofline, lowest
chip (four-chip cell)."""
from bench.readers import ell_spmv_roofline as read  # noqa: F401
