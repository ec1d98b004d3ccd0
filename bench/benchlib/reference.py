"""The plain reference decoder and the comparison that decides
``correct``.

``viterbi`` is textbook maximum-likelihood Viterbi in ``jax.numpy``:
one stage per scan step, two predecessors per state, branch metrics the
correlation of the LLRs with the +-1 code symbols, one decision bit per
state and stage, traceback from the best state at each frame's own last
stage.  Its trellis comes from the polynomials alone (``channel``'s
conventions); it imports nothing of the program.  It runs in float32
adds and maxes, which no device rounds below float32.  With
``dtype=bfloat16`` the same decoder keeps its LLRs and path metrics in
bfloat16: that is the control, the reference in the nearest precision
below the float32 the configurations state, put in the program's place.

``path_gap`` is the number compared: how far, in LLR units, the metric
of the program's decoded bits lies below the metric of the reference's
bits over the same stages, summed in float64 on the host.  A decoder
that returns the maximum-likelihood path reads 0; one that rounds its
metrics reads 0 except where a rounding flips a near-tie, and then the
gap is about the size of the rounding.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["viterbi", "depuncture", "encode_np", "path_gap"]

_NEG = -1e30


def _branch_tables(polys: Sequence[int], k: int):
    """Predecessors and code-symbol signs of the two branches into each
    state.  State = the last k-1 inputs, the newest in the MSB."""
    S = 1 << (k - 1)
    new = np.arange(S)
    u = new >> (k - 2)
    preds, signs = [], []
    for low in (0, 1):
        p = ((new << 1) & (S - 1)) | low
        reg = (u << (k - 1)) | p
        bits = np.stack([_parity(reg & g) for g in polys], axis=-1)
        preds.append(p)
        signs.append(1.0 - 2.0 * bits)  # (S, beta)
    return np.stack(preds), np.stack(signs).astype(np.float32)


def _parity(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x).copy()
    p = np.zeros_like(x)
    while np.any(x):
        p ^= x & 1
        x >>= 1
    return p


@functools.partial(jax.jit,
                   static_argnames=("polys", "k", "start_zero", "dtype"))
def _viterbi(llrs, ends, polys, k, start_zero, dtype):
    F, n, _ = llrs.shape
    S = 1 << (k - 1)
    preds, signs = _branch_tables(polys, k)
    preds = jnp.asarray(preds)
    signs = jnp.asarray(signs, dtype)
    llrs = llrs.astype(dtype)
    lam0 = jnp.zeros((F, S), dtype)
    if start_zero:
        lam0 = jnp.full((F, S), _NEG, dtype).at[:, 0].set(0.0)
    best0 = jnp.zeros((F,), jnp.int32)

    def fwd(carry, xs):
        lam, best = carry
        l_t, t = xs  # (F, beta)
        # +-1 branch sums as elementwise adds: exact in float32 on any
        # device (a TPU's default f32 matmul would round them to bf16)
        m0 = lam[:, preds[0]] + jnp.sum(l_t[:, None, :] * signs[0], -1)
        m1 = lam[:, preds[1]] + jnp.sum(l_t[:, None, :] * signs[1], -1)
        d = m1 > m0
        lam = jnp.where(d, m1, m0)
        lam = lam - jnp.max(lam, axis=1, keepdims=True)
        best = jnp.where(ends - 1 == t, jnp.argmax(lam, axis=1).astype(
            jnp.int32), best)
        return (lam, best), d

    (_, best), dec = jax.lax.scan(
        fwd, (lam0, best0),
        (jnp.transpose(llrs, (1, 0, 2)), jnp.arange(n, dtype=jnp.int32)),
    )
    rows = jnp.arange(F)

    def back(state, xs):
        d_t, t = xs
        state = jnp.where(ends - 1 == t, best, state)
        live = t < ends
        bit = jnp.where(live, state >> (k - 2), 0)
        prev = ((state << 1) & (S - 1)) | d_t[rows, state].astype(jnp.int32)
        return jnp.where(live, prev, state), bit

    _, bits = jax.lax.scan(
        back, jnp.zeros((F,), jnp.int32),
        (dec, jnp.arange(n, dtype=jnp.int32)), reverse=True,
    )
    return bits.T.astype(jnp.uint8)


def viterbi(llrs: np.ndarray, polys: Sequence[int], k: int,
            start_zero: bool, ends: Optional[np.ndarray] = None,
            dtype=jnp.float32) -> np.ndarray:
    """ML decisions of F frames: llrs (F, n, beta) -> (F, n) uint8.

    Frame f ends after stage ``ends[f] - 1`` (default n) and traces back
    from its best state there; its later stages read 0.  ``start_zero``
    pins the start state to 0, else all states start equal.  ``dtype``
    holds the LLRs and path metrics (bfloat16: the control)."""
    llrs = jnp.asarray(llrs, jnp.float32)
    F, n, _ = llrs.shape
    ends = np.full(F, n) if ends is None else np.asarray(ends)
    out = _viterbi(llrs, jnp.asarray(ends, jnp.int32),
                   tuple(int(g) for g in polys), int(k), bool(start_zero),
                   jnp.dtype(dtype))
    return np.asarray(out)


def depuncture(kept: np.ndarray, mask, n_stages: int, beta: int
               ) -> np.ndarray:
    """Sent LLRs -> (n_stages, beta), 0 where a bit was not sent (a zero
    LLR favours neither symbol).  Without a mask every bit was sent."""
    out = np.zeros(n_stages * beta, np.float32)
    if mask is None:
        out[:] = np.reshape(kept, -1)
    else:
        m = np.asarray(mask, dtype=bool)
        reps = -(-n_stages // m.shape[0])
        sent = np.tile(m, (reps, 1))[:n_stages].reshape(-1)
        out[np.flatnonzero(sent)] = kept
    return out.reshape(n_stages, beta)


def encode_np(bits: np.ndarray, polys: Sequence[int], k: int) -> np.ndarray:
    """(n,) bits from state 0 -> (n, beta) code bits."""
    bits = np.asarray(bits, np.int64)
    n = bits.shape[0]
    ext = np.concatenate([np.zeros(k - 1, np.int64), bits])
    out = np.zeros((n, len(polys)), np.int64)
    for j, g in enumerate(polys):
        for d in range(k):
            if (g >> (k - 1 - d)) & 1:
                out[:, j] ^= ext[k - 1 - d: k - 1 - d + n]
    return out


def path_gap(llrs: np.ndarray, ref_bits: np.ndarray, prog_bits: np.ndarray,
             polys: Sequence[int], k: int) -> float:
    """Metric of ``ref_bits`` minus metric of ``prog_bits`` over the
    stages of ``llrs`` (n, beta), both paths re-encoded from state 0.
    Sequences that agree read exactly 0.  Where they share their first
    k-1 bits, the start state does not enter the difference."""
    ref_bits = np.asarray(ref_bits)
    prog_bits = np.asarray(prog_bits)
    if np.array_equal(ref_bits, prog_bits):
        return 0.0
    diff = encode_np(prog_bits, polys, k) - encode_np(ref_bits, polys, k)
    # metric = sum LLR * (1 - 2c): ref - prog = sum 2 LLR (c_prog - c_ref)
    return float(np.sum(2.0 * np.asarray(llrs, np.float64) * diff))
