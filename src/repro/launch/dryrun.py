import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input-shape)
cell on the production meshes, and extract the roofline inputs.

For each cell this produces a JSON record with:
  * compiled memory_analysis (bytes per device — proves it fits)
  * compiled cost_analysis (HLO FLOPs / bytes accessed)
  * collective-bytes by op kind, parsed from the optimized HLO
  * MODEL_FLOPS (6·N_active·D) and the analytic executed-FLOPs breakdown

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""
import argparse
import json
import re
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, get_config, input_specs, list_configs
from repro.flops.accounting import model_flops_6nd, step_flops
from repro.launch.mesh import axes_of, make_ctx, make_production_mesh
from repro.launch.sharding import (batch_shardings, opt_state_shardings,
                                   param_shardings)
from repro.models import api as models
from repro.optim import adamw
from repro.train.steps import init_opt_state, make_prefill_step, \
    make_serve_step, make_train_step


from repro.launch.hlo_analysis import analyze as analyze_hlo


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------
def parallelism_for(cfg, shape, mesh, policy: str = "auto"):
    """(dp_axes, tp_axis) per arch/shape — the §Perf cell-A optimization.

    Small dense models (≤ ~8B params) are communication-bound under 16-way
    TP at 256 chips (measured 424 GiB/device/step of TP-boundary wire on
    granite train_4k); pure DP+FSDP over BOTH mesh axes cuts that ~20x.
    Big / MoE / head-heavy models keep the TP axis.  policy="baseline"
    reproduces the paper-faithful TP16 layout for §Perf before/after.
    """
    dp, tp = axes_of(mesh)
    if policy == "baseline":
        return dp, tp
    from repro.flops.accounting import param_count_analytic
    small = param_count_analytic(cfg) < 8e9
    # ssm/hybrid excluded: their (B,nc,nh,Q,Q) SSD intermediates need the
    # head-sharded TP layout (pure-DP measured 2.5x WORSE memory on zamba2
    # train — §Perf cell C iteration 1, refuted)
    pure_dp_ok = (small and shape.kind == "train"
                  and cfg.family in ("dense", "vlm", "encdec"))
    if pure_dp_ok:
        return dp + (tp,), None
    return dp, tp


def build_cell(arch: str, shape_name: str, mesh, *, fsdp: bool = True,
               opt_cfg: adamw.OptConfig | None = None,
               policy: str = "auto"):
    """Returns (jitted fn, arg ShapeDtypeStructs + shardings) for one cell."""
    from repro.models.common import ShardCtx
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not cfg.supports_shape(shape):
        return None
    dp, tp = parallelism_for(cfg, shape, mesh, policy)

    # serving mode (§Perf cell B): EP² experts over the full mesh; drop
    # FSDP when the non-expert weights fit TP-sharded + replicated.
    # DECODE ONLY: at prefill token volume the EP² dispatch gathers dwarf
    # the weight gathers it saves (measured 6x worse on v3 prefill_32k).
    serving = policy != "baseline" and shape.kind == "decode"
    ep = None
    if serving:
        from repro.flops.accounting import param_count_analytic
        dense_bytes = (param_count_analytic(cfg, active_only=True) * 2
                       / (mesh.shape[tp] if tp else mesh.size))
        if cfg.num_experts and tp is not None \
                and cfg.num_experts % mesh.size == 0:
            ep = tuple(dp) + (tp,)
        # drop FSDP only when the weights actually fit without it: experts
        # must be EP²-shardable (else they'd replicate over data — measured
        # 238 GiB/dev on v3 decode at 512 chips where 256 % 512 != 0)
        experts_ok = not cfg.num_experts or ep is not None
        fsdp = fsdp and not (dense_bytes < 8e9 and experts_ok)
    ctx = ShardCtx(mesh=mesh, dp=dp, tp=tp, ep=ep)

    aparams = models.abstract_params(cfg)
    p_sh = param_shardings(cfg, aparams, mesh, dp, tp, fsdp,
                           serving=serving)
    b_specs = input_specs(cfg, shape)
    b_sh = batch_shardings(cfg, shape, mesh, dp, tp)

    if shape.kind == "train":
        big = cfg.num_layers * cfg.d_model > 250_000
        if opt_cfg is None:
            opt_cfg = adamw.OptConfig(
                moment_dtype="bfloat16" if big else "float32",
                factored_v=big)
        # gradient accumulation for the giants: activations scale with the
        # microbatch; fp32 grad accumulator is FSDP-sharded
        accum = 4 if big else 1
        aopt = jax.eval_shape(partial(init_opt_state, opt_cfg), aparams)
        o_sh = opt_state_shardings(aopt, mesh, dp, tp, fsdp)
        # explicit out_shardings: without them the partitioner may produce
        # REPLICATED grads (all-reduce) instead of reduce-scattering into
        # the FSDP-sharded update (§Perf cell A, iteration 2)
        fn = jax.jit(make_train_step(cfg, opt_cfg, ctx, accum_steps=accum),
                     in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None),
                     donate_argnums=(0, 1))
        args = (aparams, aopt, b_specs)
    elif shape.kind == "prefill":
        fn = jax.jit(make_prefill_step(cfg, ctx), in_shardings=(p_sh, b_sh))
        args = (aparams, b_specs)
    else:  # decode
        fn = jax.jit(make_serve_step(cfg, ctx), in_shardings=(p_sh, b_sh),
                     donate_argnums=(1,))
        args = (aparams, b_specs)
    return fn, args, cfg, shape


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             fsdp: bool = True, hlo_dir: str | None = None) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    built = build_cell(arch, shape_name, mesh, fsdp=fsdp)
    if built is None:
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": "full-attention arch skips long_500k (DESIGN.md)"}
    fn, args, cfg, shape = built

    t0 = time.time()
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    st = analyze_hlo(hlo, n_dev)
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{'multi' if multi_pod else 'single'}"
        with open(os.path.join(hlo_dir, tag + ".hlo"), "w") as f:
            f.write(hlo)

    analytic = step_flops(cfg, shape, executed=True,
                          remat=(cfg.remat != "none"))
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": n_dev,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0)
                           + getattr(mem, "temp_size_in_bytes", 0)),
        },
        # raw XLA cost analysis counts while bodies ONCE (see hlo_analysis)
        "cost_raw": {"flops": cost.get("flops", 0.0),
                     "bytes_accessed": cost.get("bytes accessed", 0.0)},
        # trip-count-aware per-device stats from the optimized HLO text
        "hlo": {"flops": st.flops,
                "traffic_bytes": st.traffic_bytes,
                "collective_bytes": st.collective_bytes,
                "collective_counts": st.collective_counts},
        "model_flops_6nd": model_flops_6nd(cfg, shape),
        "analytic_mxu_flops": analytic.total_mxu,
        "analytic_vpu_flops": analytic.total_vpu,
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--hlo-dir", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in list_configs():
            for s in SHAPES:
                cells.append((a, s))
    else:
        cells.append((args.arch, args.shape))

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}_{shape}_{'multi' if mp else 'single'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip-cached] {tag}")
                continue
            try:
                rec = run_cell(arch, shape, multi_pod=mp,
                               fsdp=not args.no_fsdp, hlo_dir=args.hlo_dir)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                if rec.get("skipped"):
                    print(f"[skipped ] {tag}: {rec['reason']}")
                else:
                    print(f"[ok      ] {tag}: "
                          f"hlo_flops={rec['hlo']['flops']:.3e} "
                          f"peak_mem={rec['memory']['peak_bytes'] / 2**30:.2f}GiB "
                          f"compile={rec['compile_s']}s")
            except Exception as e:
                failures += 1
                print(f"[FAILED  ] {tag}: {type(e).__name__}: {e}")
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
