"""engine_host_share.mbps: Share of the traced interval in which the engine's host code ran (engine.poll and engine.submit, less engine.device_wait) in the decoded_mbps cells."""
from benchlib.spans import engine_host_share


def read(run):
    return engine_host_share(run.spans, run.t0, run.t1)
