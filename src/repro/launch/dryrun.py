"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST set the host-platform device count before ANY other import (jax locks
the device count on first init).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402
import argparse
import functools
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import roofline
from repro.configs import (
    ALL_IDS,
    ARCH_IDS,
    SHAPE_CELLS,
    cell_applicable,
    get_config,
    input_specs,
)
from repro.configs import viterbi_k7 as vit
from repro.distributed import sharding as shd
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models import lm
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.serve.step import (
    make_decode_step,
    make_prefill_step,
    make_viterbi_serve_step,
)
from repro.train.step import make_train_step

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def mesh_name(multi_pod: bool) -> str:
    return "2pod-2x16x16" if multi_pod else "1pod-16x16"


def model_flops(cfg, cell) -> float:
    """MODEL_FLOPS convention (EXPERIMENTS.md §Roofline): 6*N*D for train
    (N = active params for MoE), 2*N*D for forward-only inference."""
    n = cfg.n_active_params() if cfg.n_experts else cfg.n_params()
    if cell.kind == "train":
        return 6.0 * n * cell.global_batch * cell.seq_len
    if cell.kind == "prefill":
        return 2.0 * n * cell.global_batch * cell.seq_len
    return 2.0 * n * cell.global_batch  # decode: one token per stream


def viterbi_model_flops(vcfg, cell) -> float:
    """Useful ACS work: per stage per state, 2^rho predecessors x
    (branch-metric MACs + add + compare).  Standard cells (DESIGN.md §7)
    adjust the stage count for puncturing (stream_len counts KEPT serial
    LLRs) and for the WAVA circulations of tail-biting cells."""
    from repro.codes.registry import get_code
    from repro.codes.tailbiting import DEFAULT_WAVA_ITERS

    spec, rho = vcfg.spec, vcfg.rho
    S, R, B = spec.n_states, 1 << rho, rho * spec.beta
    code = get_code(getattr(cell, "code", "ccsds-k7"))
    if code.termination == "tailbiting":
        stages = cell.stream_len * DEFAULT_WAVA_ITERS  # batch WAVA passes
    else:
        n = cell.stream_len
        v = vcfg.overlap
        if code.puncture is not None:
            n = code.puncture.stages_for(cell.stream_len)
            # the lowered program tiles with the erasure-stretched
            # overlap (ViterbiDecoder.default_tiled_config, DESIGN.md §7)
            v = int(v * code.puncture.expansion)
            v += (-v) % rho
        n_windows = -(-n // vcfg.frame_len)  # tiled_decode_stream ceils
        stages = n_windows * (vcfg.frame_len + 2 * v)
    steps = stages / rho
    per_step = S * R * (2 * B + 2)
    return cell.batch_streams * steps * per_step


def _lower_lm_cell(cfg, cell, mesh):
    params_shape = jax.eval_shape(
        functools.partial(lm.init_params, cfg), jax.random.PRNGKey(0)
    )
    specs = input_specs(cfg, cell)
    bspecs = shd.batch_specs(cfg, mesh, cell)
    b_sh = {k: NamedSharding(mesh, bspecs[k]) for k in specs}

    if cell.kind == "train":
        opt_shape = jax.eval_shape(adamw_init, params_shape)
        p_sh, o_sh = shd.train_state_shardings(
            cfg, mesh, params_shape, opt_shape
        )
        # 4 microbatches: 256-row global batch -> 64 rows per grad-accum
        # step (4 per device on the 16-wide data axis)
        step = make_train_step(cfg, AdamWConfig(), microbatches=4)
        jitted = jax.jit(
            step,
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, None),
            donate_argnums=(0, 1),
        )
        return jitted.lower(params_shape, opt_shape, specs)

    p_sh, _ = shd.train_state_shardings(cfg, mesh, params_shape, None)
    if cell.kind == "prefill":
        cache_shape = lm.cache_specs(cfg, cell.global_batch, cell.seq_len)
        # cache specs are legal by construction (GSPMD pads uneven dims)
        cspecs = shd.cache_partition_specs(cfg, mesh, cell.global_batch)
        c_sh = shd.named(mesh, cspecs)
        step = make_prefill_step(cfg)
        jitted = jax.jit(
            step,
            in_shardings=(p_sh, c_sh, b_sh),
            out_shardings=(None, c_sh),
            donate_argnums=(1,),
        )
        return jitted.lower(params_shape, cache_shape, specs)

    # decode: one token against a seq_len-deep cache
    cache_shape = lm.cache_specs(cfg, cell.global_batch, cell.seq_len)
    cspecs = shd.cache_partition_specs(cfg, mesh, cell.global_batch)
    c_sh = shd.named(mesh, cspecs)
    step = make_decode_step(cfg)
    tok_sh = NamedSharding(mesh, bspecs["tokens"])
    jitted = jax.jit(
        step,
        in_shardings=(p_sh, c_sh, tok_sh),
        out_shardings=(None, c_sh),
        donate_argnums=(1,),
    )
    return jitted.lower(params_shape, cache_shape, specs["tokens"])


def _lower_viterbi_cell(vcfg, cell, mesh):
    # frames are embarrassingly parallel (paper §III): shard streams over
    # the largest axis prefix that divides the batch
    axes = list(dp_axes(mesh)) + ["model"]
    while axes:
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if cell.batch_streams % size == 0:
            break
        axes.pop()
    dp = tuple(axes) or None
    specs = vit.input_specs(vcfg, cell)
    llr_spec = specs["llrs"]
    # tail-biting blocks decode whole (WAVA batch mode); open-trellis
    # streams tile.  Punctured cells feed rank-2 serial LLRs.
    from repro.codes.registry import get_code

    code = get_code(getattr(cell, "code", "ccsds-k7"))
    mode = "batch" if code.termination == "tailbiting" else "tiled"
    in_axes = (dp,) + (None,) * (len(llr_spec.shape) - 1)
    sh = NamedSharding(mesh, P(*in_axes))
    step = make_viterbi_serve_step(vcfg, mode=mode)
    jitted = jax.jit(
        step,
        in_shardings=(sh,),
        out_shardings=NamedSharding(mesh, P(dp, None)),
    )
    return jitted.lower(llr_spec)


def run_cell(arch: str, cell_name: str, multi_pod: bool, save: bool = True):
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    mname = mesh_name(multi_pod)
    rec = {
        "arch": arch,
        "cell": cell_name,
        "mesh": mname,
        "n_chips": n_chips,
    }
    t0 = time.time()
    try:
        if arch == "viterbi-k7":
            cell = vit.VITERBI_CELLS[cell_name]
            vcfg = vit.config_for_standard(cell.code)
            mf = viterbi_model_flops(vcfg, cell)
            with mesh:
                lowered = _lower_viterbi_cell(vcfg, cell, mesh)
                compiled = lowered.compile()
        else:
            cfg = get_config(arch)
            cell = SHAPE_CELLS[cell_name]
            if not cell_applicable(cfg, cell):
                rec["status"] = "skipped"
                rec["reason"] = (
                    "long_500k requires sub-quadratic attention; "
                    f"{arch} is pure full-attention (DESIGN.md §4)"
                )
                return rec
            mf = model_flops(cfg, cell)
            with mesh:
                lowered = _lower_lm_cell(cfg, cell, mesh)
                compiled = lowered.compile()
        report = roofline.analyze(
            arch, cell_name, mname, n_chips, compiled, mf
        )
        rec.update(report.to_dict())
        rec["status"] = "ok"
        rec["compile_s"] = round(time.time() - t0, 1)
        mem = compiled.memory_analysis()
        print(
            f"[{mname}] {arch} x {cell_name}: OK "
            f"({rec['compile_s']}s) args={mem.argument_size_in_bytes/2**30:.2f}GiB "
            f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB "
            f"bottleneck={rec['bottleneck']}"
        )
    except Exception as e:  # noqa: BLE001 — record the failure
        rec["status"] = "failed"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        print(f"[{mname}] {arch} x {cell_name}: FAILED {rec['error'][:200]}")
    finally:
        if save:
            out = OUT_DIR / mname
            out.mkdir(parents=True, exist_ok=True)
            (out / f"{arch}__{cell_name}.json").write_text(
                json.dumps(rec, indent=1, default=str)
            )
    return rec


def iter_cells(arch=None):
    archs = [arch] if arch else ALL_IDS
    for a in archs:
        if a == "viterbi-k7":
            for c in vit.VITERBI_CELLS:
                yield a, c
        else:
            for c in SHAPE_CELLS:
                yield a, c


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, choices=ALL_IDS + [None])
    ap.add_argument("--cell", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument(
        "--mesh", default="single", choices=["single", "multi", "both"]
    )
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[
        args.mesh
    ]
    cells = (
        [(args.arch, args.cell)]
        if args.arch and args.cell
        else list(iter_cells(args.arch))
    )
    results = []
    for multi_pod in meshes:
        for arch, cell in cells:
            if args.skip_existing:
                f = OUT_DIR / mesh_name(multi_pod) / f"{arch}__{cell}.json"
                if f.exists() and json.loads(f.read_text()).get("status") in (
                    "ok",
                    "skipped",
                ):
                    continue
            results.append(run_cell(arch, cell, multi_pod))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_fail = sum(r["status"] == "failed" for r in results)
    print(f"\n== dry-run: {n_ok} ok, {n_skip} skipped, {n_fail} failed ==")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
