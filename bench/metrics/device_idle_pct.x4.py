"""device_idle_pct.x4: the device's idle share of the traced window, mean over
the chips (four-chip cell)."""
from bench.readers import device_idle_pct


def read(run):
    return device_idle_pct(run, "apply")
