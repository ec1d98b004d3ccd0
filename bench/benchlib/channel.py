"""Seeded LLR generator: message bits -> convolutional encoder ->
puncturing -> BPSK + AWGN -> channel LLRs, made on the device in one
jitted call per array.

Conventions (the same as the decoder under test, written here on their
own so that the yardstick does not move with the program):

* a code is ``k`` and its generator polynomials as k-bit integers whose
  MSB taps the current input bit (the octal values printed in the
  standards);
* coded bit 0 is sent as +1, bit 1 as -1; the LLR is ``2 y / sigma^2``
  with ``sigma^2 = 1 / (2 R Eb/N0)`` at the effective rate R, so an LLR
  above 0 favours bit 0;
* a puncture mask has one row per stage and one column per coded bit
  (1 = sent); the serial stream holds the sent LLRs stage by stage.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["jax_key", "kept_index", "encode", "make_llrs", "sigma"]


def jax_key(seed: int, *path: int) -> jax.Array:
    """A threefry key for any non-negative seed (wider than 32 bits too)
    and a sub-stream path of non-negative integers."""
    words = np.random.SeedSequence([int(seed), *map(int, path)]).generate_state(
        2, np.uint32
    )
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def sigma(ebn0_db: float, rate: float) -> float:
    """Noise standard deviation of unit-energy BPSK at Eb/N0 and rate R."""
    return float(np.sqrt(1.0 / (2.0 * rate * 10.0 ** (ebn0_db / 10.0))))


def kept_index(mask: Optional[Sequence[Sequence[int]]], n_stages: int,
               beta: int) -> np.ndarray:
    """Flat indices into the (n_stages * beta) coded stream of the bits a
    puncture mask sends (all of them without a mask)."""
    if mask is None:
        return np.arange(n_stages * beta)
    m = np.asarray(mask, dtype=bool)
    reps = -(-n_stages // m.shape[0])
    return np.flatnonzero(np.tile(m, (reps, 1))[:n_stages].reshape(-1))


def encode(bits: jnp.ndarray, polys: Tuple[int, ...], k: int,
           circular: bool) -> jnp.ndarray:
    """(R, n) bits -> (R, n, beta) coded bits.  The encoder starts in
    state 0, or, with ``circular``, in the state the last k-1 bits leave
    it in (so a stream that repeats the block stays one codeword)."""
    n = bits.shape[1]
    if circular:
        head = bits[:, n - (k - 1):]
    else:
        head = jnp.zeros((bits.shape[0], k - 1), bits.dtype)
    ext = jnp.concatenate([head, bits], axis=1)  # ext[:, k-1+t] = u[t]
    outs = []
    for g in polys:
        acc = jnp.zeros_like(bits)
        for d in range(k):  # tap d multiplies u[t - d]
            if (g >> (k - 1 - d)) & 1:
                acc = acc ^ ext[:, k - 1 - d: k - 1 - d + n]
        outs.append(acc)
    return jnp.stack(outs, axis=-1)


@functools.partial(
    jax.jit,
    static_argnames=("shape", "polys", "k", "mask", "circular", "tail_len"),
)
def _llrs(key, ebn0_db, rate, shape, polys, k, mask, circular, tail_at,
          tail_len):
    rows, n = shape
    kb, kn = jax.random.split(key)
    bits = jax.random.bernoulli(kb, 0.5, (rows, n)).astype(jnp.int32)
    if tail_at is not None:  # per-row zero tail: bits [at, at + len) = 0
        col = jnp.arange(n)[None, :]
        at = tail_at[:, None]
        bits = jnp.where((col >= at) & (col < at + tail_len), 0, bits)
    coded = encode(bits, polys, k, circular).reshape(rows, -1)
    coded = coded[:, kept_index(mask, n, len(polys))]
    sd = jnp.sqrt(1.0 / (2.0 * rate * 10.0 ** (ebn0_db / 10.0)))
    y = (1.0 - 2.0 * coded.astype(jnp.float32)) + sd * jax.random.normal(
        kn, coded.shape, jnp.float32
    )
    return bits, 2.0 * y / (sd * sd)


def make_llrs(key, rows: int, n_stages: int, polys, k: int, ebn0_db: float,
              mask=None, circular: bool = False, tail_at=None,
              tail_len: int = 0):
    """(bits (rows, n_stages) int32, LLRs) on the device.

    LLRs are (rows, n_stages, beta) without a puncture mask and the
    serial (rows, kept) stream with one.  ``tail_at`` (rows,) zeroes
    ``tail_len`` message bits per row from that position (a frame's
    flush tail inside a longer block)."""
    beta = len(polys)
    mask_t = None if mask is None else tuple(tuple(int(v) for v in r)
                                             for r in mask)
    kept_per_period = beta if mask_t is None else sum(map(sum, mask_t))
    period = 1 if mask_t is None else len(mask_t)
    rate = period / kept_per_period
    bits, llrs = _llrs(
        key, jnp.float32(ebn0_db), jnp.float32(rate), (rows, n_stages),
        tuple(int(g) for g in polys), int(k), mask_t, bool(circular),
        None if tail_at is None else jnp.asarray(tail_at, jnp.int32),
        int(tail_len),
    )
    if mask_t is None:
        llrs = llrs.reshape(rows, n_stages, beta)
    return bits, llrs
