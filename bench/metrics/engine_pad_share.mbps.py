"""engine_pad_share.mbps: Share of the LLR elements the engine sent to the device that were padding (frame and length rungs), over the engine.assemble spans that count them (real_elems, cell_elems) in the decoded_mbps cells; None where no span counts them."""


def read(run):
    real = cell = 0
    for s in run.spans:
        if s.name == "engine.assemble" and "cell_elems" in s.attrs:
            real += s.attrs["real_elems"]
            cell += s.attrs["cell_elems"]
    return 100.0 * (1.0 - real / cell) if cell else None
