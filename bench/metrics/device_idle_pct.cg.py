"""device_idle_pct.cg: the device's idle share of the traced window, in CG
traffic."""
from bench.readers import device_idle_pct


def read(run):
    return device_idle_pct(run, "cg_iteration")
