"""Data pipelines.

Two sources, both deterministic and host-shardable:
  * ``TokenStream`` — synthetic LM token batches (training the assigned
    architectures end-to-end without external corpora);
  * ``ChannelStream`` — the paper's pipeline (Fig. 12): random bits ->
    convolutional encoder -> BPSK+AWGN -> LLR frames, for the Viterbi
    decoder service and the BER farm.

Determinism: batch ``i`` of host ``h`` is a pure function of
(seed, h, i), so restarts resume exactly (fault tolerance) and any host
can regenerate any shard (elastic re-sharding).
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CODE_K7_CCSDS, CodeSpec
from repro.core import channel as ch
from repro.core.encoder import conv_encode_jax

__all__ = ["TokenStream", "ChannelStream"]


@dataclasses.dataclass
class TokenStream:
    """Synthetic LM batches with a Zipfian unigram + bigram structure so
    that loss decreases measurably during the example training runs."""

    vocab_size: int
    batch: int
    seq_len: int
    seed: int = 0
    host_id: int = 0
    n_hosts: int = 1
    prefix_len: int = 0
    d_model: int = 0

    def __iter__(self) -> Iterator[dict]:
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1

    def batch_at(self, step: int) -> dict:
        key = jax.random.PRNGKey(
            (self.seed * 1_000_003 + self.host_id) * 1_000_003 + step
        )
        kz, kb, kp = jax.random.split(key, 3)
        # Zipf-ish marginal via squared uniform exponent
        u = jax.random.uniform(kz, (self.batch, self.seq_len))
        toks = (self.vocab_size * u**3).astype(jnp.int32)
        # inject determinism: every token at even pos copies prev//2
        prev = jnp.roll(toks, 1, axis=1)
        even = (jnp.arange(self.seq_len) % 2 == 0)[None, :]
        toks = jnp.where(even, (prev // 2) % self.vocab_size, toks)
        labels = jnp.roll(toks, -1, axis=1).at[:, -1].set(-1)
        out = {"tokens": toks, "labels": labels}
        if self.prefix_len:
            out["prefix_embeds"] = (
                0.02
                * jax.random.normal(
                    kp, (self.batch, self.prefix_len, self.d_model)
                )
            ).astype(jnp.bfloat16)
        return out


@dataclasses.dataclass
class ChannelStream:
    """Paper Fig. 12 transmitter + channel: yields (bits, llrs) batches.

    ``code`` names a ``repro.codes.registry`` standard (DESIGN.md §7):
    the stream is then encoded with that code's termination (tail-biting
    needs no tail), punctured to its rate, and the LLRs come back as the
    SERIAL kept stream (n_streams, Lp) — exactly what a punctured
    ``ViterbiDecoder.from_standard`` consumes.  ``code=None`` keeps the
    legacy (spec, shaped-LLR) behavior.
    """

    spec: CodeSpec = CODE_K7_CCSDS
    n_streams: int = 8
    stream_len: int = 4096
    ebn0_db: float = 4.0
    seed: int = 0
    host_id: int = 0
    code: Optional[str] = None

    def key_at(self, step: int) -> jax.Array:
        """The PRNG discipline (DESIGN.md §11): batch ``step`` of shard
        ``host_id`` draws from ``fold_in(fold_in(PRNGKey(seed),
        host_id), step)``.  ``fold_in`` is a keyed hash, so distinct
        (host_id, step) pairs give independent streams — per-shard keys
        are DISJOINT by construction (no arithmetic collisions), and the
        schedule is a pure function of (seed, host_id, step): restarts
        resume exactly and any host regenerates any shard."""
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), self.host_id)
        return jax.random.fold_in(key, step)

    def shard(self, host_id: int) -> "ChannelStream":
        """This stream re-keyed for shard ``host_id`` — the per-shard
        split the sharded BER farm (repro.verify) fans out over."""
        return dataclasses.replace(self, host_id=host_id)

    def batch_at(self, step: int):
        kb, kn = jax.random.split(self.key_at(step))
        bits = jax.random.bernoulli(
            kb, 0.5, (self.n_streams, self.stream_len)
        ).astype(jnp.int32)
        if self.code is not None:
            from repro.codes import encode_standard, get_code, standard_llrs

            code = get_code(self.code)
            coded = encode_standard(bits, code)
            return bits, standard_llrs(kn, coded, self.ebn0_db, code)
        coded = conv_encode_jax(bits, self.spec)
        rx = ch.awgn(kn, ch.bpsk(coded), self.ebn0_db, self.spec.rate)
        llrs = ch.llr(rx, self.ebn0_db, self.spec.rate)
        return bits, llrs

    def __iter__(self):
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1
