"""device_idle.mbps: Device idle share of the traced window in the decoded_mbps cells: 1 - (union of device-op intervals) / window."""
from benchlib.layers import device_idle


def read(run):
    return device_idle(run)
