"""1 - union of device-op intervals / traced window, mean over chips."""
from bench.harness.readers import device_idle_frac


def read(run):
    return device_idle_frac(run)
