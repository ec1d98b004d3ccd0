"""Standard-codes subsystem (DESIGN.md §7): the registry of deployed
convolutional codes (CCSDS/DVB-S/802.11a/LTE TBCC/GSM), puncturing /
rate-matching, and tail-biting (WAVA) decode — all behind the
``ViterbiDecoder`` front door via ``ViterbiDecoder.from_standard``."""
from .puncture import (  # noqa: F401
    PuncturePattern,
    depuncture,
    depuncture_np,
    puncture,
)
from .registry import (  # noqa: F401
    REGISTRY,
    StandardCode,
    get_code,
    list_codes,
)
from .simulate import (  # noqa: F401
    encode_standard,
    measure_standard_ber,
    standard_llrs,
    tx_frames,
)
from .tailbiting import tail_bite_state, wava_decode  # noqa: F401
