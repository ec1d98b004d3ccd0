"""decoder_host_ms.mbps: Host milliseconds per engine dispatch spent in the decoder's own spans (decoder.*) in the decoded_mbps cells."""
from benchlib.spans import decoder_host_ms


def read(run):
    return decoder_host_ms(run.spans)
