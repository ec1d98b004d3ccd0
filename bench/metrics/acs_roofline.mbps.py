"""acs_roofline.mbps: ACS kernels' share of their roofline in the decoded_mbps cells."""
from benchlib.layers import acs_roofline


def read(run):
    return acs_roofline(run)
