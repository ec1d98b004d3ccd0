"""viterbi-k7 — the paper's own workload as a first-class config (§IX-A):
code (2,1,7), polynomials (171,133) octal, soft-decision, radix-4 packed
tensor-ACS, frame tiling f=64 / v=32.

serve_step = tiled tensor-ACS decode of a batch of LLR streams; dry-run and
rooflined on the same production meshes as the LM architectures.
"""
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import CODE_K7_CCSDS, CodeSpec, TiledDecoderConfig


@dataclasses.dataclass(frozen=True)
class ViterbiConfig:
    name: str = "viterbi-k7"
    family: str = "viterbi"
    spec: CodeSpec = CODE_K7_CCSDS
    # registry standard this config serves (repro.codes.registry); the
    # decoder front door inherits its puncture pattern and termination
    code: str = "ccsds-k7"
    rho: int = 2
    frame_len: int = 64
    overlap: int = 32
    # serving shapes: a batch of independent LLR streams
    stream_len: int = 1 << 16  # stages per stream
    batch_streams: int = 512
    # §Perf C knobs (paper Table I / output compaction analogues)
    channel_bf16: bool = False  # C1: bf16 LLR blocks + matmul inputs
    pack_survivors: bool = False  # C2: 16 x 2-bit survivors per int32
    renorm: bool = True  # C3: per-step path-metric renormalization
    split_dot: bool = False  # C5: bf16 branch metrics + f32 metric routing
    # time-parallel decode (DESIGN.md §9): None = auto-select by shape.
    # Kernel geometry has no field here: it follows the shape
    # (core/kernel_geometry.py).
    time_parallel: Optional[bool] = None

    @property
    def tiled(self) -> TiledDecoderConfig:
        return TiledDecoderConfig(
            frame_len=self.frame_len, overlap=self.overlap, rho=self.rho
        )

    @property
    def precision(self):
        from repro.core.viterbi import AcsPrecision
        import jax.numpy as jnp

        if self.channel_bf16:
            return AcsPrecision(
                matmul_dtype=jnp.bfloat16,
                channel_dtype=jnp.bfloat16,
                renorm=self.renorm,
                split_dot=self.split_dot,
            )
        return AcsPrecision(renorm=self.renorm, split_dot=self.split_dot)


CONFIG = ViterbiConfig()  # paper-faithful baseline (Table I single-prec)

# §Perf C4b: the adopted optimized service config — bf16 channel, packed
# survivors, f=128 frames; BER bit-identical to baseline
CONFIG_OPTIMIZED = ViterbiConfig(
    name="viterbi-k7-opt",
    frame_len=128,
    channel_bf16=True,
    pack_survivors=True,
)


def config_for_standard(name: str, **overrides) -> ViterbiConfig:
    """A ViterbiConfig serving one registry standard (DESIGN.md §7):
    spec, puncture and termination all follow the registry entry."""
    from repro.codes.registry import get_code

    code = get_code(name)
    kw = dict(name=f"viterbi-{name}", spec=code.spec, code=name)
    kw.update(overrides)
    return ViterbiConfig(**kw)


@dataclasses.dataclass(frozen=True)
class ViterbiCell:
    name: str
    stream_len: int
    batch_streams: int
    kind: str = "decode"
    code: str = "ccsds-k7"  # registry standard the cell serves


# the paper's workload cells: short LTE-like blocks up to DVB-like
# streams, plus one cell per deployed standard (code×rate grid)
VITERBI_CELLS = {
    "decode_64k": ViterbiCell("decode_64k", 1 << 16, 512),
    "decode_1m": ViterbiCell("decode_1m", 1 << 20, 32),
    # punctured streams: stream_len is the KEPT (serial) LLR count
    "decode_64k_wifi_r34": ViterbiCell(
        "decode_64k_wifi_r34", 1 << 16, 512, code="wifi-11a-r34"
    ),
    "decode_64k_dvb_r78": ViterbiCell(
        "decode_64k_dvb_r78", 1 << 16, 512, code="dvb-s-r78"
    ),
    # tail-biting control blocks are short; batch is correspondingly deep
    "decode_tbcc_blocks": ViterbiCell(
        "decode_tbcc_blocks", 128, 8192, code="lte-tbcc"
    ),
    "decode_gsm_bursts": ViterbiCell(
        "decode_gsm_bursts", 456, 4096, code="gsm-cs1"
    ),
}


def input_specs(cfg: ViterbiConfig, cell: ViterbiCell):
    """Serving-shape ShapeDtypeStructs for a cell.  Punctured cells feed
    the SERIAL kept-LLR stream (batch, Lp); unpunctured cells the shaped
    (batch, n, beta) LLRs."""
    from repro.codes.registry import get_code

    code = get_code(cell.code)
    if code.puncture is not None:
        shape = (cell.batch_streams, cell.stream_len)
    else:
        shape = (cell.batch_streams, cell.stream_len, code.spec.beta)
    return {"llrs": jax.ShapeDtypeStruct(shape, jnp.float32)}


def smoke_config() -> ViterbiConfig:
    return ViterbiConfig(
        name="viterbi-k7-smoke", stream_len=512, batch_streams=4
    )
