"""The comparison catches a broken timed path: each fault the cells can
have, planted under a full run of the harness (no chip needed), turns
``correct`` false.  The cells run on one chip, so there is no exchange
between chips to leave out."""
import numpy as np
import pytest

from repro.core.decoder import ViterbiDecoder
from repro.serve import engine as engine_mod
from tiny import run_tiny


def _flip_answers(monkeypatch):
    """An answer altered where it is produced: one bit of every
    dispatch's first ticket."""
    run_batch = engine_mod.DecodeEngine._run_batch
    group = engine_mod.DecodeEngine._dispatch_session_group

    def flip(tickets):
        for t in tickets:
            if t.bits is not None and t.bits.size:
                t.bits = t.bits.copy()
                t.bits[t.bits.size // 2] ^= 1
                return

    def run_batch_flip(self, *a, **k):
        out = run_batch(self, *a, **k)
        flip(out)
        return out

    def group_flip(self, *a, **k):
        out, ok = group(self, *a, **k)
        flip(out)
        return out, ok

    monkeypatch.setattr(engine_mod.DecodeEngine, "_run_batch",
                        run_batch_flip)
    monkeypatch.setattr(engine_mod.DecodeEngine, "_dispatch_session_group",
                        group_flip)


def _stale_state(monkeypatch, advance_pos):
    """A step that returns its state unchanged (the stream position
    advanced or not)."""
    orig = ViterbiDecoder.decode_chunk_multi

    def stale(self, states, chunks):
        new, outs = orig(self, states, chunks)
        if not advance_pos:
            return list(states), outs
        return [type(s)(lam=s.lam, hist=s.hist, pos=n.pos)
                for s, n in zip(states, new)], outs

    monkeypatch.setattr(ViterbiDecoder, "decode_chunk_multi", stale)


def _half_batch(monkeypatch):
    """Half of the batch left out: the second half of every dispatch's
    frames never decoded (their bits left at zero)."""
    multi = ViterbiDecoder.decode_chunk_multi
    batch = ViterbiDecoder.decode_batch

    def multi_half(self, states, chunks):
        h = max(1, len(states) // 2)
        new, outs = multi(self, states[:h], chunks[:h])
        rest = [np.zeros_like(np.asarray(o)) for o in outs[: len(states) - h]]
        return new + list(states[h:]), outs + rest

    def batch_half(self, llrs, *a, **k):
        out = np.array(batch(self, llrs, *a, **k))
        out[out.shape[0] // 2:] = 0
        return out

    monkeypatch.setattr(ViterbiDecoder, "decode_chunk_multi", multi_half)
    monkeypatch.setattr(ViterbiDecoder, "decode_batch", batch_half)


SESSIONS = ["ccsds.links256", "ccsds.links8"]
FRAMES = ["wifi.steady", "wifi.closed64"]


@pytest.mark.parametrize("cell_name", SESSIONS + FRAMES)
def test_altered_answer_fails(cell_name, monkeypatch):
    _flip_answers(monkeypatch)
    assert not run_tiny(cell_name)["correct"]


@pytest.mark.parametrize("advance_pos", [False, True])
@pytest.mark.parametrize("cell_name", SESSIONS)
def test_unchanged_state_fails(cell_name, advance_pos, monkeypatch):
    _stale_state(monkeypatch, advance_pos)
    assert not run_tiny(cell_name)["correct"]


@pytest.mark.parametrize("cell_name", SESSIONS + FRAMES)
def test_half_batch_fails(cell_name, monkeypatch):
    _half_batch(monkeypatch)
    assert not run_tiny(cell_name)["correct"]
