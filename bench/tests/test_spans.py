"""The span readers on hand-built span lists whose answers are worked
out by hand."""
import dataclasses
import itertools

import pytest

from benchlib import spans

_ids = itertools.count(1)


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    parent: object = None
    attrs: dict = dataclasses.field(default_factory=dict)
    id: int = dataclasses.field(default_factory=lambda: next(_ids))


def _kid(parent, name, t0, t1, **attrs):
    return Span(name, t0, t1, parent.id, attrs)


def _session_batch(t0, stack, split, wait, n):
    """engine.batch [t0, t0+10) > engine.dispatch [t0+1, t0+9) >
    decoder.stack/validate/launch/split + engine.device_wait."""
    b = Span("engine.batch", t0, t0 + 10)
    d = _kid(b, "engine.dispatch", t0 + 1, t0 + 9, h2d_arrays=0)
    return [
        b, _kid(b, "engine.assemble", t0, t0 + 1), d,
        _kid(d, "decoder.stack", t0 + 1, t0 + 1 + stack, h2d_arrays=n),
        _kid(d, "decoder.validate", t0 + 4, t0 + 5),
        _kid(d, "decoder.launch", t0 + 5, t0 + 6),
        _kid(d, "decoder.split", t0 + 6, t0 + 6 + split),
        _kid(d, "engine.device_wait", t0 + 9 - wait, t0 + 9),
    ]


def test_union_merges_overlaps_and_clips():
    iv = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (5.5, 5.7), (8.0, 8.0)]
    assert spans.union_s(iv) == pytest.approx(4.0)
    assert spans.union_s(iv, 1.5, 5.5) == pytest.approx(2.0)
    assert spans.union_s([]) == 0.0


def test_decoder_host_ms_sums_decoder_spans_per_batch():
    run = (_session_batch(0.0, stack=2, split=1, wait=1, n=4)
           + _session_batch(20.0, stack=1, split=2, wait=1, n=4))
    # batch 1: 2 + 1 + 1 + 1 = 5 s, batch 2: 1 + 1 + 1 + 2 = 5 s
    assert spans.decoder_host_ms(run) == pytest.approx(5e3)
    # a nested decoder span counts inside its parent, once
    (launch,) = [s for s in run[:8] if s.name == "decoder.launch"]
    run.append(_kid(launch, "decoder.inner", 5.2, 5.4))
    assert spans.decoder_host_ms(run) == pytest.approx(5e3)
    # a batch with no decoder spans (a route without them) is left out
    run.append(Span("engine.batch", 40.0, 41.0))
    assert spans.decoder_host_ms(run) == pytest.approx(5e3)


def test_engine_host_share_unions_less_device_wait_and_clips():
    poll = Span("engine.poll", 2.0, 12.0)
    b = _kid(poll, "engine.batch", 3.0, 11.0)
    w = _kid(b, "engine.device_wait", 6.0, 9.0)
    sub = [Span("engine.submit", 0.5, 1.0), Span("engine.submit", 1.5, 2.5)]
    run = [poll, b, w] + sub
    # host: [0.5, 1) + [1.5, 12) less [6, 9) = 0.5 + 7.5 = 8 of [0, 20)
    assert spans.engine_host_share(run, 0.0, 20.0) == pytest.approx(40.0)
    # clipped to [1, 10): [1.5, 6) + [9, 10) = 5.5 of 9
    assert spans.engine_host_share(run, 1.0, 10.0) == pytest.approx(
        100 * 5.5 / 9)


def test_h2d_arrays_mean_per_batch_over_descendants():
    run = (_session_batch(0.0, stack=2, split=1, wait=1, n=256)
           + _session_batch(20.0, stack=2, split=1, wait=1, n=8))
    assert spans.h2d_arrays(run) == pytest.approx((256 + 8) / 2)
    b = Span("engine.batch", 40.0, 50.0)
    d = _kid(b, "engine.dispatch", 41.0, 49.0, h2d_arrays=1)
    lp = _kid(d, "decoder.depuncture", 41.5, 42.0, h2d_arrays=0)
    assert spans.h2d_arrays([b, d, lp]) == pytest.approx(1.0)


def test_none_without_the_spans():
    # what the parent program leaves: engine spans without the new ones
    old = [Span("engine.batch", 0.0, 10.0)]
    old.append(_kid(old[0], "engine.dispatch", 1.0, 9.0))
    old.append(_kid(old[1], "engine.device_wait", 2.0, 8.0))
    for run in ([], old):
        assert spans.decoder_host_ms(run) is None
        assert spans.engine_host_share(run, 0.0, 10.0) is None
        assert spans.h2d_arrays(run) is None
    assert spans.engine_host_share(
        [Span("engine.poll", 0.0, 1.0)], 5.0, 5.0) is None
