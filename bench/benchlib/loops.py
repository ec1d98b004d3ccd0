"""The traffic generator: it reads a traffic file's parameters and
drives ``DecodeEngine`` in one of three loops.

* ``sessions`` -- closed loop of streaming sessions (``open_session`` /
  ``submit_chunk`` / ``poll``): every session always has one chunk
  queued; its next chunk is queued as soon as the last one completes.
  Chunks cycle through a seeded pool that is one circular codeword, so
  every session's stream is one continuous codeword.  A punctured code's
  chunks are its serial kept-LLR stream, whole pattern periods each.
* ``open`` -- open-loop arrivals of whole frames (``submit`` / ``poll``)
  at a fixed mean rate, Poisson, or in on/off bursts where the file has
  ``burst``.  Every seed gets the same multiset of frame sizes and of
  gaps between arrivals, in its own order, so the work in a window does
  not depend on the seed.
* ``closed`` -- a fixed number of frames outstanding: each completion
  sends the next frame of a seeded pool.  The pool holds the mix's
  sizes stratified, ordered so that each block of ``clients`` frames
  holds one frame of every stratum: the work a stretch of the window
  meets does not depend on the seed.

A mix that these parameters cannot express brings ``traffic/<mix>.py``
beside its ``.json``, with a ``make_driver(traffic, config, seed,
seconds)`` that may subclass the drivers here (``spec.traffic_module``).

Every answer records when it was due and when the client held its bits
(after the ``poll`` that returned it).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import channel, reference
from .spec import codes_of, traffic_module

__all__ = ["Answer", "SessionDriver", "FrameDriver", "driver_for",
           "make_driver"]

clock = time.perf_counter


@dataclasses.dataclass
class Answer:
    """One request or chunk a client sent: when it was due, its work,
    and when the client held its bits."""

    due: float
    ticket: object  # the engine's Ticket
    stages: int  # trellis stages decoded for it
    llr_count: int  # LLRs it carried
    info_bits: int  # information bits it delivers to its client
    key: tuple  # how to rebuild its LLRs for the check
    code: str = ""
    held: Optional[float] = None
    in_window: bool = False


class _Annotate:
    """``jax.profiler.TraceAnnotation`` in traced runs, a no-op else."""

    def __init__(self, on: bool):
        self.on = on
        if on:
            import jax

            self._ann = jax.profiler.TraceAnnotation

    def __call__(self, name: str):
        return self._ann(name) if self.on else contextlib.nullcontext()


# ---------------------------------------------------------------------------
# sessions


class SessionDriver:
    """Closed-loop streaming sessions over a circular chunk pool."""

    def __init__(self, traffic: dict, codes: dict, ebn0: dict, seed: int):
        self.t = traffic
        self.code = traffic["code"]
        self.c = codes[self.code]
        self.n_sessions = int(traffic["sessions"])
        self.chunk = int(traffic["chunk_stages"])
        self.n_pool = int(traffic["pool_chunks"])
        self.mask = self.c.get("puncture")
        if self.mask is not None and self.chunk % len(self.mask):
            raise ValueError(f"chunk_stages {self.chunk} is not whole "
                             f"puncture periods of {len(self.mask)}")
        rng = np.random.default_rng([seed, 1])
        # where each session starts in the pool: all apart while they fit
        self.offsets = rng.permutation(self.n_pool)[
            np.arange(self.n_sessions) % self.n_pool]
        _, llrs = channel.make_llrs(
            channel.jax_key(seed, 2), 1, self.n_pool * self.chunk,
            self.c["polys"], self.c["k"], ebn0[self.code], mask=self.mask,
            circular=True,
        )
        # (pool, chunk, beta) stages, or (pool, kept) serial chunks
        self.pool = np.asarray(llrs).reshape(
            (self.n_pool, self.chunk, -1) if self.mask is None
            else (self.n_pool, -1))
        self.sids: List[str] = []
        self.sent_chunks = np.zeros(self.n_sessions, np.int64)
        self.emitted = np.zeros(self.n_sessions, np.int64)
        self.answers: List[Answer] = []
        self._by_ticket: Dict[int, int] = {}  # ticket id -> answer index

    def _fetch(self, j: int, i: int) -> np.ndarray:
        return self.pool[(self.offsets[j] + i) % self.n_pool]

    def _send(self, engine, j: int, now: float, ann) -> None:
        i = int(self.sent_chunks[j])
        with ann("bench.fetch"):
            x = self._fetch(j, i)
        with ann("bench.submit"):
            tk = engine.submit_chunk(self.sids[j], x, now=now)
        self.sent_chunks[j] += 1
        self._by_ticket[tk.id] = len(self.answers)
        self.answers.append(Answer(
            due=now, ticket=tk, stages=self.chunk,
            llr_count=x.size, info_bits=0, key=(j, i), code=self.code,
        ))

    def _collect(self, done, held: float) -> None:
        for tk in done:
            a = self.answers[self._by_ticket.pop(tk.id)]
            a.held = held
            j, i = a.key
            n = 0 if tk.bits is None else int(tk.bits.shape[0])
            a.info_bits = n
            # emitted span [e0, e1) of the session's stream, and the
            # stream front the decisions could look ahead to
            a.key = (j, i, int(self.emitted[j]), int(self.emitted[j]) + n,
                     (i + 1) * self.chunk)
            self.emitted[j] += n

    def warm(self, engine, rounds: int = 2) -> int:
        """Open the sessions and run ``rounds`` chunks each: the first
        chunk of a stream emits fewer bits than later ones, so two
        rounds reach every program the window runs.  Returns the number
        of fused session dispatches made."""
        for j in range(self.n_sessions):
            self.sids.append(engine.open_session(self.code, sid=f"s{j:04d}"))
        for _ in range(rounds):
            now = clock()
            for j in range(self.n_sessions):
                self._send(engine, j, now, _Annotate(False))
            self._collect(engine.poll(clock()), clock())
        return rounds

    def window(self, engine, t0: float, seconds: float, ann,
               on_tick: Callable[[float], None]) -> float:
        """Run until ``seconds`` have passed; returns the window's end
        (the end of the last poll that started inside it)."""
        t_end = t0 + seconds
        now = t0
        last = t0
        while now < t_end:
            for j in range(self.n_sessions):
                self._send(engine, j, now, ann)
                self.answers[-1].in_window = True
            with ann("bench.poll"):
                done = engine.poll(now)
            last = clock()
            self._collect(done, last)
            on_tick(last)
            now = last
        return last

    def finish(self, engine) -> None:
        """Drain whatever is still queued (nothing, in a closed loop)."""
        if self._by_ticket:
            self._collect(engine.drain(clock()), clock())

    def check_items(self):
        """(answer, session stream window) pairs for the comparison."""
        return [a for a in self.answers if a.in_window]

    def stream_llrs(self, j: int, lo: int, hi: int) -> np.ndarray:
        """Stages [lo, hi) of session j's stream, (hi - lo, beta), a
        punctured code's unsent bits as 0."""
        beta = len(self.c["polys"])
        parts = []
        s = lo
        while s < hi:
            i, off = divmod(s, self.chunk)
            take = min(hi - s, self.chunk - off)
            stages = reference.depuncture(self._fetch(j, i), self.mask,
                                          self.chunk, beta)
            parts.append(stages[off: off + take])
            s += take
        return np.concatenate(parts, axis=0)


# ---------------------------------------------------------------------------
# whole frames (open and closed loops)


def _mix_quantile(frames: List[dict], u: float):
    """The frame class and PSDU length at quantile ``u`` of the mix."""
    cum = 0.0
    for f in frames:
        if u < cum + f["share"] or f is frames[-1]:
            v = min(max((u - cum) / f["share"], 0.0), 1.0 - 1e-12)
            c2 = 0.0
            for p, lo, hi in f["len"]:
                if v < c2 + p or [p, lo, hi] == list(f["len"][-1]):
                    w = min(max((v - c2) / p, 0.0), 1.0 - 1e-12)
                    return f["code"], lo + int(w * (hi - lo + 1))
                c2 += p
        cum += f["share"]
    raise ValueError("empty frame mix")


def _blocks(n: int, width: int, rng) -> np.ndarray:
    """An order of n stratified requests in which every run of ``width``
    that starts at a multiple of ``width`` holds one request of each of
    ``width`` strata (n // width neighbouring quantiles each): a closed
    loop that keeps ``width`` requests outstanding then meets the same
    work in every stretch of its pool, whatever the seed."""
    if n % width:
        raise ValueError(f"pool_requests {n} is not a multiple of "
                         f"clients {width}")
    strata = np.arange(n).reshape(width, n // width)
    # which member of each stratum goes to which block, then the order
    # inside each block
    blocks = rng.permuted(strata, axis=1).T
    return rng.permuted(blocks, axis=1).reshape(-1)


class FrameDriver:
    """Whole PPDU DATA fields as requests: SERVICE + PSDU + tail + pad,
    N_SYM * N_DBPS message bits, N_SYM = ceil((service + 8 LEN + tail) /
    N_DBPS), the tail zeroed and the pad after it."""

    def __init__(self, traffic: dict, config: dict, codes: dict, ebn0: dict,
                 seed: int, seconds: float):
        self.t = traffic
        self.codes = codes
        fr = config["framing"]
        self.service, self.tail = int(fr["service_bits"]), int(fr["tail_bits"])
        self.slo = traffic["slo"]
        self.loop = traffic["loop"]
        if self.loop == "open":
            self.rate = float(traffic["rate_per_s"])
            n = int(math.ceil(self.rate * (seconds + traffic["drain_s"])))
        else:
            n = int(traffic["pool_requests"])
        rng = np.random.default_rng([seed, 3])
        # stratified sizes: the same multiset for every seed
        sizes = [_mix_quantile(traffic["frames"], (i + 0.5) / n)
                 for i in range(n)]
        if self.loop == "open":
            order = rng.permutation(n)
        else:
            order = _blocks(n, int(traffic["clients"]), rng)
        self.reqs = [sizes[i] for i in order]
        if self.loop == "open":
            self.offsets = self.arrivals(n, rng)
        # message and LLR arrays per code, one row per request
        self.rows: List[tuple] = []  # (code, row, n_stages, psdu bits)
        self.data: Dict[str, np.ndarray] = {}
        by_code: Dict[str, List[int]] = {}
        for r, (code, length) in enumerate(self.reqs):
            by_code.setdefault(code, []).append(r)
        self.rows = [None] * n
        for ci, (code, idx) in enumerate(sorted(by_code.items())):
            c = codes[code]
            nst = [self._stages(c, self.reqs[r][1]) for r in idx]
            tail_at = [self.service + 8 * self.reqs[r][1] for r in idx]
            _, llrs = channel.make_llrs(
                channel.jax_key(seed, 4, ci), len(idx), max(nst), c["polys"],
                c["k"], ebn0[code], mask=c.get("puncture"),
                tail_at=np.asarray(tail_at), tail_len=self.tail,
            )
            self.data[code] = np.asarray(llrs)
            for row, r in enumerate(idx):
                self.rows[r] = (code, row, nst[row], 8 * self.reqs[r][1])
        self.max_stages: Dict[str, int] = {}
        for code, _, nst, _ in self.rows:
            self.max_stages[code] = max(self.max_stages.get(code, 0), nst)
        self.answers: List[Answer] = []
        self._open: Dict[int, Answer] = {}

    def arrivals(self, n: int, rng) -> np.ndarray:
        """Offsets of n arrivals from the window's start: Poisson at the
        mean rate, its gaps stratified (the same multiset for every
        seed) in the seed's order.  With ``burst`` (``on_s``, ``off_s``)
        they come in the on periods only, at the rate that keeps the
        mean."""
        rate, burst = self.rate, self.t.get("burst")
        if burst:
            on, off = float(burst["on_s"]), float(burst["off_s"])
            rate *= (on + off) / on
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
        t = np.cumsum(rng.permutation(gaps))
        if burst:
            t += np.floor(t / on) * off
        return t

    def _stages(self, c: dict, length: int) -> int:
        n_dbps = int(c["n_dbps"])
        return -(-(self.service + 8 * length + self.tail) // n_dbps) * n_dbps

    def llrs(self, r: int) -> np.ndarray:
        """Request r's LLRs as the client sends them: (n, beta) stages,
        or the serial sent stream for a punctured code."""
        code, row, nst, _ = self.rows[r]
        c = self.codes[code]
        if c.get("puncture") is None:
            return self.data[code][row, :nst]
        m = np.asarray(c["puncture"])
        kept = nst // m.shape[0] * int(m.sum())
        return self.data[code][row, :kept]

    def _request(self, r: int):
        from repro.serve.engine import DecodeRequest

        return DecodeRequest(llrs=self.llrs(r), code=self.rows[r][0],
                             slo=self.slo)

    def _send(self, engine, r: int, due: float, now: float, ann) -> Answer:
        with ann("bench.fetch"):
            req = self._request(r)
        with ann("bench.submit"):
            tk = engine.submit(req, now=now)
        code, _, nst, psdu = self.rows[r]
        a = Answer(due=due, ticket=tk, stages=nst,
                   llr_count=req.llrs.size, info_bits=psdu, key=(r,),
                   code=code)
        self.answers.append(a)
        if tk.done or tk.dropped:
            a.held = now
        else:
            self._open[tk.id] = a
        return a

    def _collect(self, done, held: float) -> List[Answer]:
        out = []
        for tk in done:
            a = self._open.pop(tk.id, None)
            if a is not None:
                a.held = held
                out.append(a)
        return out

    def warm_shapes(self):
        """The decode programs the traffic can reach: {(code, length
        rung): a request of that rung} and the frame rungs."""
        from repro.core.kernel_geometry import pick_cell_frames, pick_cell_length

        max_batch = self._engine.max_batch
        rungs: Dict[tuple, int] = {}
        for r, (code, _, nst, _) in enumerate(self.rows):
            x = self.llrs(r)
            c = self.codes[code]
            mult = 1 if c.get("puncture") is None else int(
                np.asarray(c["puncture"]).sum())
            rung = pick_cell_length(x.shape[0], self._engine.min_cell, mult)
            rungs.setdefault((code, rung), r)
        frames = sorted({pick_cell_frames(f, max_batch)
                         for f in range(1, max_batch + 1)})
        return rungs, frames

    def warm(self, engine) -> int:
        """Decode every (length rung, frame rung) program once; returns
        how many."""
        self._engine = engine
        rungs, frames = self.warm_shapes()
        from repro.serve.engine import DecodeRequest

        n = 0
        for (code, _), r in sorted(rungs.items()):
            req = DecodeRequest(llrs=self.llrs(r), code=code, slo=self.slo)
            for f in frames:
                for _ in range(f):
                    engine.submit(req, now=0.0)
                engine.drain(now=0.0)
                n += 1
        return n

    def window(self, engine, t0: float, seconds: float, ann,
               on_tick: Callable[[float], None]) -> float:
        if self.loop == "open":
            return self._window_open(engine, t0, seconds, ann, on_tick)
        return self._window_closed(engine, t0, seconds, ann, on_tick)

    def _window_open(self, engine, t0, seconds, ann, on_tick) -> float:
        t_end = t0 + seconds
        due = t0 + self.offsets
        n = len(due)
        i = 0
        wait = engine.max_wait[self.slo]
        self.late: List[float] = []
        while True:
            now = clock()
            while i < n and due[i] <= now:
                a = self._send(engine, i, due[i], now, ann)
                a.in_window = due[i] < t_end
                if a.in_window:
                    self.late.append(now - due[i])
                i += 1
            with ann("bench.poll"):
                done = engine.poll(now)
            held = clock()
            self._collect(done, held)
            on_tick(held)
            if held >= t_end and not any(a.in_window for a in
                                         self._open.values()):
                return t_end
            if held > t_end + 60.0 or (i >= n and not self._open):
                return t_end
            nxt = due[i] if i < n else held + wait
            pause = min(nxt - clock(), wait / 2)
            if pause > 0:
                with ann("bench.wait"):
                    time.sleep(pause)

    def _window_closed(self, engine, t0, seconds, ann, on_tick) -> float:
        t_end = t0 + seconds
        n = len(self.rows)
        clients = int(self.t["clients"])
        k = 0
        now = t0
        for _ in range(clients):
            self._send(engine, k % n, now, now, ann).in_window = True
            k += 1
        last = t0
        while now < t_end:
            with ann("bench.poll"):
                done = engine.poll(now)
            last = clock()
            for _ in self._collect(done, last):
                self._send(engine, k % n, last, last, ann).in_window = (
                    last < t_end)
                k += 1
            on_tick(last)
            now = clock()
        return last

    def finish(self, engine, timeout: float = 60.0) -> None:
        """Complete every answer still open (a closed loop leaves its
        outstanding frames; they are not counted in the window)."""
        t_stop = clock() + timeout
        while self._open and clock() < t_stop:
            self._collect(engine.drain(clock()), clock())

    def check_items(self):
        return [a for a in self.answers if a.in_window]


def driver_for(cell, seed: int, seconds: float):
    """The driver of a cell's traffic: its own ``traffic/<mix>.py``
    where there is one, else the loops here."""
    mod = traffic_module(cell.traffic_name)
    make = make_driver if mod is None else mod.make_driver
    return make(cell.traffic, cell.config, seed, seconds)


def make_driver(traffic: dict, config: dict, seed: int, seconds: float):
    codes = codes_of(config)
    if traffic["loop"] == "sessions":
        return SessionDriver(traffic, codes, config["ebn0_db"], seed)
    return FrameDriver(traffic, config, codes, config["ebn0_db"], seed,
                       seconds)
