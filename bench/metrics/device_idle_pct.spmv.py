"""device_idle_pct.spmv: the device's idle share of the traced window, in apply
traffic (one-chip cells)."""
from bench.readers import device_idle_pct


def read(run):
    return device_idle_pct(run, "apply")
