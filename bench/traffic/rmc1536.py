"""The UMTS 12.2 kb/s uplink RMC mix (3GPP TS 25.104 Annex A.2): whole
code blocks of the rate-1/3 K=9 code as frame requests.

A frame is one transport block (TB) + its CRC + the 8 zero tail bits,
as many trellis stages as message bits (the DTCH 244 + 16 + 8 = 268,
the DCCH 100 + 12 + 8 = 120).  The 802.11 framing of ``FrameDriver``
counts LEN in bytes and pads to OFDM symbols, which these sizes are
not, so this driver keeps its loops and checks and builds the pool in
bits: classes stratified by their shares as ``loops._blocks`` orders
them, the tail zeroed at the end of each frame, and the TB bits alone
counted as the frame's information bits (the CRC and the tail are
excluded, as the PSDU excludes SERVICE and tail)."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchlib import channel
from benchlib.loops import Answer, FrameDriver, _blocks
from benchlib.spec import codes_of


def frame_classes(traffic: dict, config: dict, n: int) -> List[tuple]:
    """(code, stages, TB bits) of n stratified requests, in quantile
    order: each class takes its share of the n, rounded by quantile."""
    fr = config["framing"]
    crc, tail = fr["crc_bits"], int(fr["tail_bits"])
    out = []
    for i in range(n):
        u, cum = (i + 0.5) / n, 0.0
        for f in traffic["frames"]:
            cum += f["share"]
            if u < cum or f is traffic["frames"][-1]:
                tb = int(f["tb_bits"])
                out.append((f["code"], tb + int(crc[f["channel"]]) + tail,
                            tb))
                break
    return out


class CodeBlockDriver(FrameDriver):
    """``FrameDriver``'s closed loop over zero-tailed code blocks whose
    sizes are counted in bits."""

    def __init__(self, traffic: dict, config: dict, codes: dict,
                 ebn0: dict, seed: int, seconds: float):
        self.t = traffic
        self.codes = codes
        self.slo = traffic["slo"]
        self.loop = traffic["loop"]
        if self.loop != "closed":
            raise ValueError(f"{self.loop!r} loop: this mix runs closed")
        self.tail = int(config["framing"]["tail_bits"])
        n = int(traffic["pool_requests"])
        rng = np.random.default_rng([seed, 3])
        sizes = frame_classes(traffic, config, n)
        order = _blocks(n, int(traffic["clients"]), rng)
        self.reqs = [sizes[i] for i in order]
        by_code: Dict[str, List[int]] = {}
        for r, (code, _, _) in enumerate(self.reqs):
            by_code.setdefault(code, []).append(r)
        self.rows: List[tuple] = [None] * n  # (code, row, stages, TB bits)
        self.data: Dict[str, np.ndarray] = {}
        for ci, (code, idx) in enumerate(sorted(by_code.items())):
            c = codes[code]
            nst = [self.reqs[r][1] for r in idx]
            _, llrs = channel.make_llrs(
                channel.jax_key(seed, 4, ci), len(idx), max(nst),
                c["polys"], c["k"], ebn0[code],
                tail_at=np.asarray(nst) - self.tail, tail_len=self.tail,
            )
            self.data[code] = np.asarray(llrs)
            for row, r in enumerate(idx):
                self.rows[r] = (code, row, nst[row], self.reqs[r][2])
        self.max_stages: Dict[str, int] = {}
        for code, _, nst, _ in self.rows:
            self.max_stages[code] = max(self.max_stages.get(code, 0), nst)
        self.answers: List[Answer] = []
        self._open: Dict[int, Answer] = {}


def make_driver(traffic: dict, config: dict, seed: int, seconds: float):
    return CodeBlockDriver(traffic, config, codes_of(config),
                           config["ebn0_db"], seed, seconds)
