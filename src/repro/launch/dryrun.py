import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e) + roofline point collection (g).

For every (arch x shape x mesh) cell:

  1. FULL compile (lax.scan layer stacks): proves the sharding config is
     coherent at depth — memory_analysis (bytes/device), collective
     schedule, compile wall time. This is the dry-run gate.
  2. Roofline points: the same program UNROLLED at 1x and 2x the block
     pattern; XLA cost_analysis counts while-bodies once, so per-repeat
     costs come from the 2x-1x difference and extrapolate linearly to full
     depth (exact for homogeneous stacks; see roofline/analysis.py).
     sLSTM time-scans are corrected analytically.

Results append incrementally to --out (JSON), keyed "arch/shape/mesh",
so reruns skip completed cells.

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun.json
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, applicable_shapes, arch_shape_cells, get_config
from repro.configs.base import ModelConfig, ShapeConfig, SLSTM
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import (
    constrainer_ctx,
    plan_for,
    shardings_for,
    train_plan_for,
)
from repro.launch.specs import batch_spec_shardings, batch_specs, decode_input_specs
from repro.models import lm
from repro.models.layers import ParallelPlan
from repro.optim import AdamWConfig, init_opt_state, opt_state_specs
from repro.serve.serve_step import make_decode_step, make_prefill_step
from repro.train.train_step import make_train_step

from jax.sharding import PartitionSpec as P


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


# ---------------------------------------------------------------------------
# cell construction: returns (lowered,) per variant
# ---------------------------------------------------------------------------

def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, plan: ParallelPlan,
               microbatches: int = 1, cache_dtype=jnp.bfloat16,
               moe_a2a: bool = False):
    """Lower one cell on one mesh. Returns jax .lower() result."""
    tplan = train_plan_for(cfg)
    opt = AdamWConfig(moment_dtype=_dtype(tplan.moment_dtype))

    pspecs = lm.param_specs(cfg, plan)
    if shape.is_train:
        pdt = _dtype(tplan.param_dtype)
        params_shape = jax.eval_shape(
            lambda: lm.init_params(jax.random.PRNGKey(0), cfg, plan, dtype=pdt)
        )
        opt_shape = jax.eval_shape(lambda: init_opt_state(params_shape, opt))
        ospecs = opt_state_specs(pspecs)
        bspecs = batch_specs(cfg, shape)
        bshard = batch_spec_shardings(cfg, shape, plan)

        p_sh = shardings_for(pspecs, params_shape, mesh)
        o_sh = shardings_for(ospecs, opt_shape, mesh)
        b_sh = shardings_for(bshard, bspecs, mesh)

        step = make_train_step(cfg, plan, opt, microbatches=microbatches)
        with constrainer_ctx(mesh, plan, moe_a2a=moe_a2a):
            jitted = jax.jit(
                step, in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, None),
                donate_argnums=(0, 1),   # params/opt update in place
            )
            return jitted.lower(params_shape, opt_shape, bspecs)

    # inference: params in bf16
    params_shape = jax.eval_shape(
        lambda: lm.init_params(jax.random.PRNGKey(0), cfg, plan, dtype=jnp.bfloat16)
    )
    p_sh = shardings_for(pspecs, params_shape, mesh)
    state_shape = jax.eval_shape(
        lambda: lm.init_decode_state(
            cfg, plan, shape.global_batch, shape.seq_len, cache_dtype=cache_dtype
        )
    )
    sspecs = lm.decode_state_specs(cfg, plan, cache_dtype=cache_dtype)
    s_sh = shardings_for(sspecs, state_shape, mesh)

    if shape.kind == "prefill":
        bspecs = batch_specs(cfg, shape)
        bshard = batch_spec_shardings(cfg, shape, plan)
        b_sh = shardings_for(bshard, bspecs, mesh)
        stepfn = make_prefill_step(cfg, plan)
        with constrainer_ctx(mesh, plan, moe_a2a=moe_a2a):
            jitted = jax.jit(
                stepfn, in_shardings=(p_sh, b_sh, s_sh), out_shardings=(None, s_sh)
            )
            return jitted.lower(params_shape, bspecs, state_shape)

    # decode: one token against a seq_len cache
    din = decode_input_specs(cfg, shape)
    tok_sh = shardings_for({"t": P(plan.dp_axes)}, {"t": din["tokens"]}, mesh)["t"]
    stepfn = make_decode_step(cfg, plan)
    rng_shape = jax.ShapeDtypeStruct((2,), jnp.uint32)
    with constrainer_ctx(mesh, plan, moe_a2a=moe_a2a):
        jitted = jax.jit(
            stepfn,
            in_shardings=(p_sh, s_sh, tok_sh, None, None),
            out_shardings=(tok_sh, None, s_sh),
            donate_argnums=(1,),     # KV cache updates in place
        )
        return jitted.lower(
            params_shape, state_shape, din["tokens"], din["pos"], rng_shape
        )


# ---------------------------------------------------------------------------
# analytic corrections for time-scans cost_analysis cannot see
# ---------------------------------------------------------------------------

def slstm_flops_correction(cfg: ModelConfig, shape: ShapeConfig, n_layers: int,
                           n_chips: int) -> float:
    """sLSTM scans over time; add its per-token gate/recurrence FLOPs."""
    kinds = cfg.layer_kinds[:n_layers]
    n_sl = sum(1 for k in kinds if k == SLSTM)
    if n_sl == 0:
        return 0.0
    d = cfg.d_model
    dh = d // cfg.n_heads
    per_tok_fwd = 2 * (4 * d * d + 4 * d * dh + 8 * d)
    mult = 3.0 if shape.is_train else 1.0
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    return n_sl * tokens * per_tok_fwd * mult / n_chips


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_kind: str, do_roofline: bool = True,
             cache_dtype_name: str = "bfloat16", moe_a2a: bool = False,
             xlstm_chunk: int = 0) -> dict:
    from repro.roofline.analysis import cost_point, extrapolate, model_flops

    cache_dtype = {"bfloat16": jnp.bfloat16, "int8": jnp.int8}[cache_dtype_name]
    cfg = get_config(arch)
    if xlstm_chunk:
        cfg = dataclasses.replace(cfg, xlstm_chunk=xlstm_chunk)
    shape = SHAPES[shape_name]
    if shape.kind == "decode":
        # §Perf A2 note: all_to_all dispatch REGRESSES single-token decode
        # (fixed-minimum per-expert buffers >> 1 token/chip); measured on
        # kimi decode_32k: t_coll 0.11 -> 5.22 s. Keep GSPMD for decode.
        moe_a2a = False
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    plan = plan_for(cfg, mesh)
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "chips": n_chips, "plan": {"tp": plan.tp, "fsdp": plan.fsdp},
    }

    # -- 1. FULL compile (the dry-run gate) ---------------------------------
    # Training cells auto-scale gradient-accumulation microbatches until the
    # step fits 16 GiB HBM; the escalation path is recorded.
    from repro.roofline.analysis import collective_bytes
    from repro.roofline.peaks import V5E

    hbm = V5E.hbm_bytes
    mb_trail = []
    if shape.is_train:
        dp_total = n_chips // plan.tp
        mb_cap = max(1, shape.global_batch // dp_total)
        mb_options = [m for m in (1, 4, 8, 16, 32) if m <= mb_cap] or [1]
    else:
        mb_options = [1]
    for mb in mb_options:
        t0 = time.time()
        lowered = lower_cell(cfg, shape, mesh, plan, microbatches=mb,
                             cache_dtype=cache_dtype, moe_a2a=moe_a2a)
        lower_s = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        compile_s = round(time.time() - t1, 1)
        ma = compiled.memory_analysis()
        peak = int(
            ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes
        )
        mb_trail.append({"microbatches": mb, "peak_per_device": peak})
        if peak <= hbm or mb == mb_options[-1]:
            break
        del compiled, lowered

    rec["lower_s"], rec["compile_s"] = lower_s, compile_s
    rec["microbatches"] = mb
    rec["microbatch_trail"] = mb_trail
    rec["memory"] = {
        "argument_bytes_per_device": int(ma.argument_size_in_bytes),
        "output_bytes_per_device": int(ma.output_size_in_bytes),
        "temp_bytes_per_device": int(ma.temp_size_in_bytes),
        "alias_bytes_per_device": int(ma.alias_size_in_bytes),
        "approx_peak_per_device": peak,
        "fits_hbm_16g": bool(peak <= hbm),
    }
    rec["full_collectives"] = collective_bytes(compiled.as_text())["counts"]
    # fusion-aware HBM traffic floor: every argument byte is read once; train
    # additionally writes params/opt back. XLA:CPU "bytes accessed" is
    # fusion-blind and overestimates; this floor brackets reality from below.
    k = 3.0 if shape.is_train else 1.0
    rec["t_memory_floor_s"] = k * ma.argument_size_in_bytes / V5E.hbm_bytes_per_s
    del compiled, lowered

    if not do_roofline:
        return rec

    # -- 2. roofline points: unrolled 1x / 2x pattern -----------------------
    pat = len(cfg.block_pattern)
    pts = []
    for mult in (1, 2):
        rcfg = dataclasses.replace(
            cfg, n_layers=pat * mult, unroll_layers=True
        )
        lw = lower_cell(rcfg, shape, mesh, plan, cache_dtype=cache_dtype,
                        moe_a2a=moe_a2a)
        pts.append(cost_point(lw.compile()))
        del lw
    n_rep_full = cfg.n_layers / pat
    terms = extrapolate(pts[0], pts[1], 1, 2, n_rep_full)
    terms.flops_per_chip += slstm_flops_correction(cfg, shape, cfg.n_layers, n_chips)

    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mf = model_flops(cfg.active_param_count(), tokens, shape.is_train)
    rec["roofline"] = terms.as_dict()
    rec["roofline"]["model_flops_per_chip"] = mf / n_chips
    rec["roofline"]["useful_flops_ratio"] = (
        (mf / n_chips) / terms.flops_per_chip if terms.flops_per_chip else 0.0
    )
    rec["roofline"]["points"] = pts
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-roofline", action="store_true")
    ap.add_argument("--cache-dtype", default="bfloat16", choices=["bfloat16", "int8"])
    ap.add_argument("--moe-dispatch", default="gspmd", choices=["gspmd", "a2a"])
    ap.add_argument("--xlstm-chunk", type=int, default=0)
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = arch_shape_cells()
    else:
        cfg = get_config(args.arch)
        shapes = [args.shape] if args.shape else applicable_shapes(cfg)
        cells = [(args.arch, s) for s in shapes]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    for arch, shape_name in cells:
        for mesh_kind in meshes:
            key = f"{arch}/{shape_name}/{mesh_kind}"
            if key in results and "error" not in results[key]:
                print(f"[skip] {key}")
                continue
            print(f"[run ] {key}", flush=True)
            t0 = time.time()
            try:
                rec = run_cell(arch, shape_name, mesh_kind,
                               do_roofline=not args.no_roofline,
                               cache_dtype_name=args.cache_dtype,
                               moe_a2a=(args.moe_dispatch == "a2a"),
                               xlstm_chunk=args.xlstm_chunk)
                rec["wall_s"] = round(time.time() - t0, 1)
                results[key] = rec
                rl = rec.get("roofline", {})
                print(
                    f"  ok {rec['wall_s']}s compile={rec['compile_s']}s "
                    f"peak/dev={rec['memory']['approx_peak_per_device']/2**30:.2f}GiB "
                    f"bottleneck={rl.get('bottleneck', '-')}",
                    flush=True,
                )
            except Exception as e:
                results[key] = {"error": f"{type(e).__name__}: {e}",
                                "traceback": traceback.format_exc()[-2000:]}
                print(f"  FAIL {type(e).__name__}: {e}", flush=True)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)

    n_ok = sum(1 for v in results.values() if "error" not in v)
    print(f"done: {n_ok}/{len(results)} cells ok")


if __name__ == "__main__":
    main()
