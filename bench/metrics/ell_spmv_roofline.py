"""ell_spmv_roofline: the ELL slot loop's share of its HBM roofline (one-chip
cells)."""
from bench.readers import ell_spmv_roofline as read  # noqa: F401
