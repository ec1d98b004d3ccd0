"""engine_depuncture_ms.mbps: Host milliseconds per punctured session chunk spent re-inserting its erasures (the mean ``engine.depuncture`` span) in the decoded_mbps cells; None where the run holds no such span."""
import statistics


def read(run):
    ms = [1e3 * (s.t1 - s.t0) for s in run.spans
          if s.name == "engine.depuncture"]
    return statistics.fmean(ms) if ms else None
