"""The control at a size a test can hold: the plain reference in
bfloat16 (the nearest precision below the float32 the configurations
state), put in the program's place, has to fail the comparison, and the
float32 program on the same answers has to pass it.

bfloat16 departs from float32 only where it flips a near-tie, a few
times per million decoded bits at the cells' own Eb/N0, and a test
decodes far fewer bits than a cell.  So the test lowers Eb/N0, where
near-ties are denser, and lengthens the session cell's decision depth
and lead-in to match (survivors merge later there).  On the chip the
control runs at the cells' own sizes and Eb/N0 (``bench/calibrate.py
--control``)."""
import pytest

from tiny import overrides, run_tiny

_FRAMES = [
    {"share": 0.5, "code": "wifi-11a", "len": [[1.0, 100, 300]]},
    {"share": 0.5, "code": "wifi-11a-r34", "len": [[1.0, 300, 600]]},
]


def _cell(cell_name):
    if cell_name.startswith("ccsds"):
        ov = overrides(cell_name, ebn0=1.0)
        ov["config"]["engine"]["decision_depth"] = 1024
        ov["traffic"].update(sessions=16, chunk_stages=1024, pool_chunks=128)
        ov["traffic"]["check"].update(answers=512, warmup_stages=512)
    else:
        ov = overrides(cell_name, ebn0=1.0)
        ov["traffic"].update(clients=8, pool_requests=48, frames=_FRAMES)
    return ov


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("cell_name", ["ccsds.links256", "wifi.closed64"])
def test_control_fails_and_program_passes(cell_name, seed):
    out = run_tiny(cell_name, seconds=3.0, seed=seed,
                   overrides_=_cell(cell_name), control=True)
    assert out["correct"], out["checks"]  # the program
    ctl = out["control"]
    assert ctl["compared"] == out["compared"]["answers"] > 0
    assert not ctl["correct"], ctl
