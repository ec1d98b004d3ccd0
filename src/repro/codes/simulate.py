"""End-to-end standard-code pipeline (paper Fig. 12 generalized,
DESIGN.md §7): bits -> encode (zero-tail or tail-biting) -> puncture ->
BPSK + AWGN -> LLR -> depuncture-aware ViterbiDecoder decode -> BER.

Used by the CI smoke job and tests/test_codes.py.  Eb/N0 is calibrated
against the EFFECTIVE rate (puncturing raises the rate, so fewer coded
bits share the same information energy).
"""
from __future__ import annotations

import zlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import channel as ch
from repro.core.ber import BerPoint
from repro.core.encoder import conv_encode_jax
# the Fig. 12 batch generator lives in data.pipeline; re-exported here
# because it IS the standard-codes simulation front end (DESIGN.md §11)
from repro.data.pipeline import ChannelStream  # noqa: F401

from .puncture import puncture
from .registry import StandardCode, get_code

__all__ = [
    "ChannelStream",
    "tx_frames",
    "encode_standard",
    "standard_llrs",
    "measure_standard_ber",
    "point_key",
    "batch_keys",
    "sim_frame_batch",
    "count_errors",
]


def tx_frames(bits: jnp.ndarray, code: StandardCode, rho: int = 2):
    """Message bits -> transmit bits: zero-terminated codes get the k-1
    zero flush tail, rounded up to a rho multiple so a final-state pin
    stays legal; tail-biting frames transmit as-is (no tail).  The ONE
    place this bookkeeping lives — examples, benchmarks, smoke and tests
    all call it."""
    bits = jnp.asarray(bits, jnp.int32)
    if code.termination != "zero":
        return bits
    tail_len = code.spec.k - 1
    tail_len += (-(bits.shape[-1] + tail_len)) % rho
    pad = jnp.zeros(bits.shape[:-1] + (tail_len,), jnp.int32)
    return jnp.concatenate([bits, pad], axis=-1)


def encode_standard(bits: jnp.ndarray, code: StandardCode) -> jnp.ndarray:
    """(..., n) message bits -> transmitted coded bits.

    Zero-terminated codes assume the tail is already part of ``bits``
    (use ``encoder.tail_flush``); tail-biting codes need no tail.
    Returns (..., n, beta) without puncturing, (..., Lp) with.
    """
    coded = conv_encode_jax(
        bits, code.spec, tail_bite=(code.termination == "tailbiting")
    )
    if code.puncture is None:
        return coded
    return puncture(coded, code.puncture)


def standard_llrs(
    key: jax.Array, coded: jnp.ndarray, ebn0_db: float, code: StandardCode
) -> jnp.ndarray:
    """BPSK + AWGN + LLR formation at the code's EFFECTIVE rate."""
    rx = ch.awgn(key, ch.bpsk(coded), ebn0_db, code.rate)
    return ch.llr(rx, ebn0_db, code.rate)


# ---------------------------------------------------------------------------
# Monte-Carlo farm batches (DESIGN.md §11)
# ---------------------------------------------------------------------------

def point_key(seed: int, code_name: str, ebn0_db: float) -> jax.Array:
    """Base PRNG key of one (code, Eb/N0) grid point.

    ``fold_in`` chains off ``PRNGKey(seed)`` with a crc32 of the code
    name (stable across processes, unlike ``hash``) and the Eb/N0 in
    milli-dB — every grid point draws an independent noise process, and
    every DECODE PATH of the same point shares it: paths are compared at
    MATCHED noise realizations, which is what lets the regression gate
    (repro.verify.gate) treat count differences as decoder differences.
    """
    key = jax.random.fold_in(
        jax.random.PRNGKey(seed), zlib.crc32(code_name.encode()) & 0x7FFFFFFF
    )
    return jax.random.fold_in(key, int(round(ebn0_db * 1000)) & 0x7FFFFFFF)


def batch_keys(
    seed: int, code_name: str, ebn0_db: float, n_batches: int
) -> jax.Array:
    """(n_batches, 2) per-batch keys of one grid point: batch ``b`` is
    ``fold_in(point_key, b)`` REGARDLESS of which shard processes it —
    the sharded farm assigns whole batches to devices, so its aggregate
    counts equal the single-device counts exactly (integer sums over the
    identical per-batch counts, DESIGN.md §11)."""
    base = point_key(seed, code_name, ebn0_db)
    return jax.vmap(lambda b: jax.random.fold_in(base, b))(
        jnp.arange(n_batches)
    )


def sim_frame_batch(
    key: jax.Array,
    code: StandardCode,
    n_frames: int,
    n_bits: int,
    ebn0_db: float,
    rho: int = 2,
):
    """One farm batch: (bits (F, n_bits), llrs) through the standard tx
    chain — message bits -> tail (zero-terminated codes, rho-aligned) ->
    encode -> puncture -> BPSK + AWGN + LLR at the EFFECTIVE rate.

    Pure function of ``key`` with static shapes, so it traces cleanly
    under jit / scan / shard_map — the farm's inner loop.  ``llrs`` is
    (F, n_tx, beta) shaped stages, or the serial kept stream (F, Lp) for
    punctured codes (the §7 front-door convention).
    """
    kb, kn = jax.random.split(key)
    bits = jax.random.bernoulli(kb, 0.5, (n_frames, n_bits)).astype(jnp.int32)
    tx = tx_frames(bits, code, rho=rho)
    coded = encode_standard(tx, code)
    return bits, standard_llrs(kn, coded, ebn0_db, code)


def count_errors(decoded: jnp.ndarray, bits: jnp.ndarray):
    """(bit_errors, frame_errors) of a decoded batch vs the true message
    bits — ``decoded`` may carry trailing tail-bit columns; only the
    first ``bits.shape[1]`` message columns are scored.  int32 counts
    (one farm batch never approaches 2^31 bits; the cross-batch reducer
    accumulates in Python ints, DESIGN.md §11)."""
    err = decoded[:, : bits.shape[1]] != bits
    return (
        jnp.sum(err, dtype=jnp.int32),
        jnp.sum(jnp.any(err, axis=1), dtype=jnp.int32),
    )


def measure_standard_ber(
    code_or_name,
    ebn0_db: float,
    n_bits: int,
    key: jax.Array,
    n_frames: int = 16,
    use_kernel: bool = False,
    decoder: Optional[object] = None,
) -> Tuple[BerPoint, object]:
    """One BER point of the code×rate grid: ``n_frames`` frames of
    ``n_bits`` message bits each, decoded through the ViterbiDecoder
    front door.  Returns (BerPoint, decoder) so sweeps reuse the tables.
    """
    from repro.core.decoder import ViterbiDecoder

    code = code_or_name if isinstance(code_or_name, StandardCode) else (
        get_code(code_or_name)
    )
    if decoder is None:
        decoder = ViterbiDecoder.from_standard(code.name, use_kernel=use_kernel)
    kb, kn = jax.random.split(key)
    bits = jax.random.bernoulli(
        kb, 0.5, (n_frames, n_bits)
    ).astype(jnp.int32)
    tx = tx_frames(bits, code, rho=decoder.rho)
    coded = encode_standard(tx, code)
    llrs = standard_llrs(kn, coded, ebn0_db, code)
    if code.termination == "zero":
        decoded = decoder.decode_batch(llrs, initial_state=0, final_state=0)
    else:
        decoded = decoder.decode_batch(llrs)
    n_err = int(jnp.sum(decoded[:, :n_bits] != bits))
    return (
        BerPoint(ebn0_db=ebn0_db, n_bits=n_frames * n_bits, n_errors=n_err),
        decoder,
    )
