"""Per-layer arithmetic shared by the readers in ``metrics/``.  Each
function takes the traced run and returns a number, or None when the
run holds nothing to read (a reader never reports 0 for a share it
could not measure)."""
from __future__ import annotations

import statistics
from typing import Optional

__all__ = ["device_idle", "acs_roofline", "acs_work", "engine_host_ms"]

RHO = 2  # the paper's radix-4 step: two stages per [L | Lambda] . W


def device_idle(run) -> Optional[float]:
    """% of the traced window in which no operation ran on the device."""
    t = run.trace
    if t is None or t.window_s <= 0 or t.n_devices == 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def acs_work(run):
    """(operations, bytes) the decoded work needs, for the answers the
    client held inside the traced interval.  Operations: the paper's
    ACS-as-matmul product per radix-4 step, [L | Lambda] . W with L the
    rho*beta LLRs of the step, Lambda the S path metrics and W
    (rho*beta + S) x (S * 2^rho): 2 (rho beta + S) S 2^rho per step, half
    that per stage.  Bytes: the float32 LLRs in and one bit out per
    stage."""
    from benchlib.spec import codes_of

    codes = codes_of(run.config)
    ops = byt = 0.0
    for a in run.answers:
        if a.held is None or not (run.t0 <= a.held <= run.t1):
            continue
        c = codes[a.code]
        S, beta = 1 << (c["k"] - 1), len(c["polys"])
        ops += a.stages * 2 * (RHO * beta + S) * S * (1 << RHO) / RHO
        byt += 4 * a.llr_count + a.stages / 8
    return ops, byt


def acs_roofline(run) -> Optional[float]:
    """% of the ACS kernels' device time that the decoded work needs at
    the chip's peaks: max(ops / bf16 peak, bytes / HBM peak) over the
    summed device time of every ACS Pallas kernel in the window."""
    t = run.trace
    if t is None or t.kernel_s <= 0:
        return None
    peaks = run.peaks[run.device_kind]  # an unknown chip is an error
    ops, byt = acs_work(run)
    if ops <= 0:
        return None
    t_min = max(ops / peaks["bf16_flops_per_s"],
                byt / peaks["hbm_bytes_per_s"])
    return 100.0 * t_min / t.kernel_s


def engine_host_ms(run) -> Optional[float]:
    """Mean host time per engine dispatch: each ``engine.batch`` span
    (session groups included) minus its ``engine.device_wait`` part."""
    by_id = {s.id: s for s in run.spans}
    wait = {}
    for s in run.spans:
        if s.name != "engine.device_wait":
            continue
        p = by_id.get(s.parent)
        while p is not None and p.name != "engine.batch":
            p = by_id.get(p.parent)
        if p is not None:
            wait[p.id] = wait.get(p.id, 0.0) + (s.t1 - s.t0)
    own = [(s.t1 - s.t0) - wait.get(s.id, 0.0) for s in run.spans
           if s.name == "engine.batch" and s.id in wait]
    return 1e3 * statistics.fmean(own) if own else None
