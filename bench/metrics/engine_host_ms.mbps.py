"""engine_host_ms.mbps: Host milliseconds per engine dispatch (batch span minus device wait) in the decoded_mbps cells."""
from benchlib.layers import engine_host_ms


def read(run):
    return engine_host_ms(run)
