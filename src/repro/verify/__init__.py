"""Statistical verification subsystem (DESIGN.md §11).

The repo's bit-exactness tests prove two decode paths agree on the same
input; they cannot price the knobs that trade *statistical* decoding
quality — decision depth, window overlap, low-precision metrics, renorm
cadence.  This package makes BER-vs-Eb/N0 a first-class verification
axis:

  * ``BerFarm`` — a sharded Monte-Carlo farm fanning a (registry code ×
    Eb/N0 × decode path) grid across the device mesh, with a streaming
    integer reducer and Clopper-Pearson/Wilson confidence intervals from
    ``repro.core.ber``;
  * ``run_gate`` — the statistical regression gate: each accelerated
    path is compared against the reference decode at MATCHED noise
    realizations and fails when its BER confidence interval excludes
    the reference curve.

``python -m repro.verify.farm`` runs the CI smoke grid (``--full`` for
the nightly grid; ``--out`` writes the farm's trajectory as JSON).

Since DESIGN.md §14 the package also hosts the online
silent-data-corruption scrubber (``verify.scrub``): the re-encode
syndrome check + shadow re-decode two-stage detector the serving
engine samples live dispatches through, closed in CI by
``python -m repro.verify.scrub_smoke`` (the `sdc-smoke` gate).
"""
from .farm import BerFarm, FarmPoint, farm_to_json  # noqa: F401
from .gate import GateVerdict, all_pass, gate_point, run_gate  # noqa: F401
from .scrub import (  # noqa: F401
    SHADOW_RUNG,
    ScrubVerdict,
    SdcScrubber,
    corruption_weight,
    syndrome_check,
)

__all__ = [
    "BerFarm",
    "FarmPoint",
    "farm_to_json",
    "GateVerdict",
    "gate_point",
    "run_gate",
    "all_pass",
    "ScrubVerdict",
    "SdcScrubber",
    "syndrome_check",
    "corruption_weight",
    "SHADOW_RUNG",
]
