"""d2h_arrays.mbps: Device buffers copied to the host per engine dispatch (the d2h_arrays of its spans) in the decoded_mbps cells; None where no span reports them."""
import statistics

from benchlib.spans import _children, _descendants


def read(run):
    kids = _children(run.spans)
    per_batch = []
    for b in run.spans:
        if b.name != "engine.batch":
            continue
        n = [s.attrs["d2h_arrays"] for s in _descendants(b, kids)
             if "d2h_arrays" in s.attrs]
        if n:
            per_batch.append(sum(n))
    return statistics.fmean(per_batch) if per_batch else None
