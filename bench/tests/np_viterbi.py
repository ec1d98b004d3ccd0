"""The plain reference decoder: textbook maximum-likelihood Viterbi in
NumPy float64, one stage at a time, frames side by side.

It shares nothing with the program: its trellis comes from the
polynomials alone (``channel``'s conventions), branch metrics are the
correlation of the LLRs with the +-1 code symbols, and survivors are
kept as one decision bit per state and stage.  A frame decodes from a
known start state (0) or from uniform metrics, and traces back from the
best state at its own last stage.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

__all__ = ["viterbi", "depuncture"]

_NEG = -1e300


def _parity(x: np.ndarray) -> np.ndarray:
    x = x.copy()
    p = np.zeros_like(x)
    while np.any(x):
        p ^= x & 1
        x >>= 1
    return p


def depuncture(kept: np.ndarray, mask: Optional[Sequence[Sequence[int]]],
               n_stages: int, beta: int) -> np.ndarray:
    """Serial sent LLRs -> (n_stages, beta) with 0 where a bit was not
    sent (a zero LLR favours neither symbol)."""
    m = np.asarray(mask, dtype=bool)
    reps = -(-n_stages // m.shape[0])
    sent = np.tile(m, (reps, 1))[:n_stages].reshape(-1)
    out = np.zeros(n_stages * beta, np.float64)
    out[np.flatnonzero(sent)] = kept
    return out.reshape(n_stages, beta)


def viterbi(llrs: np.ndarray, polys: Sequence[int], k: int,
            start_zero: bool, ends: Optional[np.ndarray] = None
            ) -> np.ndarray:
    """ML decisions of F frames.

    llrs: (F, n, beta); ``ends`` (F,) the stage count of each frame
    (default n): frame f traces back from its best state after stage
    ``ends[f] - 1``.  ``start_zero`` pins the start state to 0, else all
    states start equal.  Returns (F, n) uint8; stages past a frame's end
    are 0."""
    llrs = np.asarray(llrs, np.float64)
    F, n, beta = llrs.shape
    ends = np.full(F, n) if ends is None else np.asarray(ends, np.int64)
    S = 1 << (k - 1)
    new = np.arange(S)
    u = new >> (k - 2)  # the input bit that enters each new state
    pred = [(new << 1) & (S - 1), ((new << 1) & (S - 1)) | 1]
    # code-symbol pattern index of each (predecessor, new state) branch
    pats = []
    for p in pred:
        reg = (u << (k - 1)) | p
        idx = np.zeros(S, np.int64)
        for j, g in enumerate(polys):
            idx |= _parity(reg & int(g)) << j
        pats.append(idx)
    pattern_bits = (np.arange(1 << beta)[:, None] >> np.arange(beta)) & 1
    signs = 1.0 - 2.0 * pattern_bits  # (P, beta)
    bm = llrs @ signs.T  # (F, n, P)

    lam = np.zeros((F, S))
    if start_zero:
        lam[:, 1:] = _NEG
    dec = np.zeros((n, F, S), dtype=bool)
    best_end = np.zeros(F, np.int64)
    rows = np.arange(F)
    for t in range(n):
        b = bm[:, t]
        m0 = lam[:, pred[0]] + b[:, pats[0]]
        m1 = lam[:, pred[1]] + b[:, pats[1]]
        d = m1 > m0
        lam = np.where(d, m1, m0)
        lam -= lam.max(axis=1, keepdims=True)
        dec[t] = d
        done = ends - 1 == t
        if done.any():
            best_end[done] = np.argmax(lam[done], axis=1)

    bits = np.zeros((F, n), np.uint8)
    state = np.zeros(F, np.int64)
    for t in range(n - 1, -1, -1):
        state = np.where(ends - 1 == t, best_end, state)
        live = t < ends
        bits[:, t] = np.where(live, state >> (k - 2), 0)
        prev = ((state << 1) & (S - 1)) | dec[t, rows, state]
        state = np.where(live, prev, state)
    return bits
