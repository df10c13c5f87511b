import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape) cell, lower + compile the real
train/prefill/serve step on the production mesh - 16x16 single-pod and
2x16x16 multi-pod - and record memory_analysis / cost_analysis /
collective-schedule roofline terms.  A cell that fails to lower or compile
is a bug in the sharding config, not an acceptable skip.

Results are cached per cell in dryrun_results/<cell>.json so the sweep is
resumable.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                  # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama_1_1b \
      --shape train_4k --multi-pod both
"""

import argparse
import json
import math
import time
import traceback

import jax

from repro.configs.base import (ARCH_IDS, SHAPES, get_config,
                                shape_applicable)
from repro.distributed import sharding
from repro.launch import roofline
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (abstract_params, decode_input_specs,
                                make_optimizer, make_prefill_step,
                                make_serve_step, make_train_step)
from repro.models.api import batch_shardings, batch_specs, build

RESULT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "dryrun_results")


def _mem_summary(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(ma, f, None)
        if v is not None:
            out[f] = int(v)
    return out


def _cost_summary(compiled):
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and (
                "flops" in k or "bytes" in k or "utilization" not in k)
            and not k.startswith("utilization")}


OPT_FLAG_FIELDS = {
    # §Perf hillclimb knobs -> config overrides (see EXPERIMENTS.md §Perf)
    "bf16probs": {"attn_probs_dtype": "bfloat16"},
    "ce_recompute": {"ce_recompute": True},
    "moe_local": {"moe_local_dispatch": True},
    "noremat": {"remat": False},
    "losschunk512": {"loss_chunk": 512},
    "qchunk": {"attn_impl": "qchunk"},
    "flashattn": {"attn_impl": "flashref"},
    "tp_bf16": {"tp_bf16_reduce": True},
    "save_proj": {"save_proj_remat": True},
    "decode_inplace": {"decode_inplace": True},
}


def _apply_opt_flags(cfg, opt_flags):
    import dataclasses
    for f in opt_flags:
        if f in OPT_FLAG_FIELDS:
            cfg = dataclasses.replace(cfg, **OPT_FLAG_FIELDS[f])
        elif f != "nofsdp":
            raise ValueError(f"unknown opt flag {f!r}")
    return cfg


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             opt_flags=()) -> dict:
    cfg = _apply_opt_flags(get_config(arch), opt_flags)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "opt_flags": list(opt_flags)}
    if not shape_applicable(cfg, shape):
        rec["status"] = "skipped"
        rec["reason"] = ("long_500k requires sub-quadratic attention; "
                         f"{cfg.name} is full-attention (DESIGN.md §4)")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = math.prod(mesh.devices.shape)
    sharding.set_mesh(mesh, multi_pod=multi_pod,
                      fsdp="nofsdp" not in opt_flags)
    api = build(cfg)
    t0 = time.time()
    try:
        pshapes, pspecs = abstract_params(api)
        p_shard = sharding.tree_shardings_for(pshapes, pspecs)
        n_params = sum(math.prod(x.shape)
                       for x in jax.tree.leaves(pshapes))
        rec["n_params"] = n_params

        if shape.kind == "train":
            opt = make_optimizer(cfg)
            oshapes = jax.eval_shape(opt.init, pshapes)
            ospecs = opt.state_specs(pspecs)
            o_shard = sharding.tree_shardings_for(oshapes, ospecs)
            bshapes = batch_specs(cfg, shape)
            b_shard = sharding.tree_shardings_for(
                bshapes, batch_shardings(cfg, shape))
            step = make_train_step(api, opt)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, o_shard, b_shard),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(pshapes, oshapes, bshapes)
        elif shape.kind == "prefill":
            bshapes = batch_specs(cfg, shape)
            b_shard = sharding.tree_shardings_for(
                bshapes, batch_shardings(cfg, shape))
            step = make_prefill_step(api)
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(pshapes, bshapes)
        else:  # decode
            (cache_s, tok_s, idx_s), (cache_t, tok_t, idx_t) = \
                decode_input_specs(api, shape)
            c_shard = sharding.tree_shardings_for(cache_s, cache_t)
            t_shard = sharding.named_sharding(tok_t)
            step = make_serve_step(api)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, c_shard, t_shard,
                                           sharding.replicated()),
                             donate_argnums=(1,))
            lowered = jitted.lower(pshapes, cache_s, tok_s, idx_s)

        rec["lower_s"] = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = time.time() - t1
        rec["memory"] = _mem_summary(compiled)
        rec["cost"] = _cost_summary(compiled)

        text = compiled.as_text()
        coll = roofline.collective_bytes(text)
        rec["collectives"] = {"total_bytes": coll.total_bytes,
                              "count": coll.count,
                              "by_kind": coll.by_kind}
        hc = roofline.hlo_cost(text)
        rec["hlo_cost"] = {k: v for k, v in hc.items()
                           if k != "multiplicities"}
        rec["scan_multiplicities"] = hc["multiplicities"]
        # XLA's cost_analysis counts while bodies once; prefer the
        # trip-count-aware HLO-text accounting (see roofline.hlo_cost).
        flops = max(rec["cost"].get("flops", 0.0), hc["dot_flops"])
        bytes_acc = max(rec["cost"].get("bytes accessed", 0.0),
                        hc["bytes"])
        rec["roofline"] = roofline.roofline_terms(
            flops, bytes_acc, coll.total_bytes, chips,
            device_kind=roofline.V5E)      # the production mesh's chip
        mf = roofline.model_flops(cfg, shape)
        rec["model_flops"] = mf
        # flops is per-device (SPMD HLO); model_flops is whole-job
        rec["useful_flops_ratio"] = (mf / chips / flops) if flops else None
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001
        rec["status"] = "failed"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    finally:
        sharding.set_mesh(None)
    rec["total_s"] = time.time() - t0
    return rec


def cell_path(arch, shape_name, multi_pod, opt_flags=()):
    tag = "mp" if multi_pod else "sp"
    suffix = ("." + ".".join(sorted(opt_flags))) if opt_flags else ""
    return os.path.join(RESULT_DIR, f"{arch}.{shape_name}.{tag}{suffix}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS))
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", default="both",
                    choices=["both", "single", "multi"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--opt", default="",
                    help="comma-separated optimization flags (perf loop)")
    args = ap.parse_args()

    os.makedirs(RESULT_DIR, exist_ok=True)
    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    pods = {"both": [False, True], "single": [False],
            "multi": [True]}[args.multi_pod]
    opt_flags = tuple(f for f in args.opt.split(",") if f)

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in pods:
                path = cell_path(arch, shape_name, mp, opt_flags)
                if os.path.exists(path) and not args.force:
                    with open(path) as f:
                        rec = json.load(f)
                else:
                    rec = run_cell(arch, shape_name, mp, opt_flags)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                tag = rec["mesh"]
                if rec["status"] == "ok":
                    n_ok += 1
                    r = rec["roofline"]
                    print(f"OK   {arch:18s} {shape_name:12s} {tag:8s} "
                          f"compute={r['compute_s']:.3e}s "
                          f"mem={r['memory_s']:.3e}s "
                          f"coll={r['collective_s']:.3e}s "
                          f"dom={r['dominant']}")
                elif rec["status"] == "skipped":
                    n_skip += 1
                    print(f"SKIP {arch:18s} {shape_name:12s} {tag}")
                else:
                    n_fail += 1
                    print(f"FAIL {arch:18s} {shape_name:12s} {tag}: "
                          f"{rec['error'][:200]}")
    print(f"\n{n_ok} ok / {n_skip} skipped / {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
