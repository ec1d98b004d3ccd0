"""``kernels.json`` names the ACS kernels as the TPU compiler names their
custom calls, which name their events in the trace: each name is the
instruction of a ``tpu_custom_call`` in the programs the cells run,
compiled for a described TPU v5e (no chip needed)."""
import json
import os
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _custom_calls(compiled) -> set:
    return {m.group(1) for m in re.finditer(
        r"%([\w.-]+?)(?:\.\d+)? = .*custom_call_target=\"tpu_custom_call\"",
        compiled.as_text())}


def test_kernel_names_are_the_compiled_instructions(one_chip, monkeypatch):
    import jax
    import jax.numpy as jnp

    import repro.core.backend as backend
    from repro.core import ViterbiDecoder
    from repro.core.decoder import _chunk_step_fused
    from repro.core.kernel_geometry import DEFAULT_BLOCK_FRAMES
    from repro.core.timeparallel import decode_time_parallel

    jax.config.update("jax_enable_compilation_cache", False)
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    names = set()
    # a session group (the one-pass kernel)
    dec = ViterbiDecoder.from_standard("ccsds-k7", use_kernel=True)
    st = dec.init_stream_state(1)
    F, c = 8, 1024
    d_steps = st.hist.shape[0]
    shape = jax.ShapeDtypeStruct
    low = _chunk_step_fused.lower(
        shape((d_steps, F, st.hist.shape[2]), st.hist.dtype,
              sharding=one_chip),
        shape((F, 64), jnp.float32, sharding=one_chip),
        shape((c // 2, F, 4), jnp.float32, sharding=one_chip),
        dec.tables, dec.precision, dec._one_pass_tile(c // 2, d_steps),
        DEFAULT_BLOCK_FRAMES, dec.ring_packed)
    names |= _custom_calls(low.compile())
    # a latency batch (the time-parallel kernels)
    dw = ViterbiDecoder.from_standard("wifi-11a-r34", use_kernel=True)
    x = shape((4, 1536, 2), jnp.float32, sharding=one_chip)
    f = jax.jit(lambda llrs: decode_time_parallel(
        llrs, dw.spec, rho=2, initial_state=0, final_state=None,
        precision=dw.precision, transfer_tile=128, use_kernel=True,
        pack_survivors=False))
    names |= _custom_calls(f.lower(x).compile())
    kernels = json.loads((BENCH / "kernels.json").read_text())["acs"]
    assert set(kernels) == names
