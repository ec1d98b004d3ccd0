"""h2d_arrays.mbps: Host arrays copied to the device per engine dispatch (the h2d_arrays of its spans) in the decoded_mbps cells."""
from benchlib.spans import h2d_arrays


def read(run):
    return h2d_arrays(run.spans)
