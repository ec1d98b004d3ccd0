"""The comparison that decides ``correct``.

Once the window has closed, a sample of the answers due in it, drawn
from the seed (the longest frames always among them), is decoded again
by the plain reference (``reference.viterbi``), and each answer is held
to two limits:

* ``missing`` -- answers that never came, were dropped, or came with an
  error or with the wrong number of bits, among all answers due in the
  window (limit 0);
* ``path_gap`` -- the widest gap, in LLR units, by which the metric of
  an answer's bits lies below the metric of the reference's bits over
  the same stages (limit from the traffic file's ``check``).

Whole frames are compared from their known start state to their own
last stage.  A session chunk's bits are compared inside a window of the
session's stream: from ``warmup_stages`` before its first emitted stage
(uniform start metrics) to the stream front when it was emitted, so the
reference looks as far ahead as the program could; the answer's bits
are spliced into the reference's path over the same window, and the two
paths' metrics differ only where the answer departs from it.

With ``control`` the same answers are also compared as the control
gives them: the same reference in bfloat16 over the same inputs, put in
the program's place (reported apart, under ``control``).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import reference

__all__ = ["compare"]


def _sample(items, n: int, seed: int, key):
    """``n`` of ``items`` drawn from the seed, the largest by ``key``
    first."""
    if len(items) <= n:
        return list(items)
    rng = np.random.default_rng([seed, 5])
    order = sorted(range(len(items)), key=lambda i: -key(items[i]))
    top = order[: max(1, n // 16)]
    rest = rng.choice(order[len(top):], n - len(top), replace=False)
    return [items[i] for i in top + sorted(rest)]


def _batches(items, size):
    for lo in range(0, len(items), size):
        yield items[lo: lo + size]


def _missing(a) -> bool:
    tk = a.ticket
    return tk.dropped or not tk.done or tk.error is not None or tk.bits is None


class _Tally:
    """Gaps and differing bits of one source of answers (the program's,
    or the control's) against the reference."""

    def __init__(self):
        self.gaps, self.bits, self.diff = [], 0, 0

    def add(self, x, ref, ans, s0: int, s1: int, c: dict) -> None:
        """``ans``: the answer's bits over stages [s0, s1) of the window
        ``x`` whose reference path is ``ref``."""
        spliced = ref.copy()
        spliced[s0:s1] = ans
        self.bits += ans.size
        self.diff += int(np.count_nonzero(ans != ref[s0:s1]))
        self.gaps.append(reference.path_gap(x, ref, spliced, c["polys"],
                                            c["k"]))

    def gap(self) -> float:
        return max(self.gaps) if self.gaps else float("inf")


def compare(driver, traffic: dict, seed: int, control: bool = False
            ) -> dict:
    chk = traffic["check"]
    items = driver.check_items()
    missing = [a for a in items if _missing(a)]
    good = [a for a in items if not _missing(a)]
    sample = _sample(good, int(chk["answers"]), seed, lambda a: a.stages)
    batch = int(chk["batch"])
    prog, ctl, wrong_len = _Tally(), _Tally(), 0

    def decode(x, **kw):
        ref = reference.viterbi(x, c["polys"], c["k"], **kw)
        alt = (reference.viterbi(x, c["polys"], c["k"], dtype=jnp.bfloat16,
                                 **kw) if control else None)
        return ref, alt

    if traffic["loop"] == "sessions":
        c = driver.c
        warm = int(chk["warmup_stages"])
        chunk = driver.chunk
        for grp in _batches(sample, batch):
            wins, spans = [], []
            for a in grp:
                j, i, e0, e1, front = a.key
                if e1 - e0 != chunk or e0 < warm or front - e1 < 0:
                    wrong_len += 1
                    continue
                lo = e0 - warm
                wins.append(driver.stream_llrs(j, lo, front))
                spans.append((a, e0 - lo, e1 - lo))
            if not wins:
                continue
            x = np.stack(wins)
            pad = batch - x.shape[0]  # keep one compiled shape
            if pad:
                x = np.concatenate([x, np.repeat(x[:1], pad, axis=0)])
            ref, alt = decode(x, start_zero=False)
            for w, (a, s0, s1) in enumerate(spans):
                prog.add(x[w], ref[w], np.asarray(a.ticket.bits, np.uint8),
                         s0, s1, c)
                if control:
                    ctl.add(x[w], ref[w], alt[w, s0:s1], s0, s1, c)
    else:
        by_code = {}
        for a in sample:
            by_code.setdefault(driver.rows[a.key[0]][0], []).append(a)
        for code, grp_all in sorted(by_code.items()):
            c = driver.codes[code]
            beta = len(c["polys"])
            n_max = driver.max_stages[code]  # one shape per code
            for grp in _batches(grp_all, batch):
                x = np.zeros((batch, n_max, beta), np.float32)
                ends = np.full(batch, n_max)
                for w, a in enumerate(grp):
                    x[w, : a.stages] = reference.depuncture(
                        driver.llrs(a.key[0]), c.get("puncture"), a.stages,
                        beta)
                    ends[w] = a.stages
                ref, alt = decode(x, start_zero=True, ends=ends)
                for w, a in enumerate(grp):
                    n = a.stages
                    bits = np.asarray(a.ticket.bits, np.uint8)
                    if bits.shape != (n,):
                        wrong_len += 1
                        continue
                    prog.add(x[w, :n], ref[w, :n], bits, 0, n, c)
                    if control:
                        ctl.add(x[w, :n], ref[w, :n], alt[w, :n], 0, n, c)
    limits = {"path_gap": float(chk["max_path_gap"]), "missing": 0}
    values = {"path_gap": prog.gap(), "missing": len(missing) + wrong_len}
    out = dict(
        correct=all(values[k] <= limits[k] for k in limits),
        values=values, limits=limits, attempted=len(items),
        failed=len(missing) + wrong_len, compared=len(prog.gaps),
        compared_bits=prog.bits, differing_bits=prog.diff,
    )
    if control:
        out["control"] = dict(
            correct=ctl.gap() <= limits["path_gap"], path_gap=ctl.gap(),
            compared=len(ctl.gaps), compared_bits=ctl.bits,
            differing_bits=ctl.diff)
    return out
