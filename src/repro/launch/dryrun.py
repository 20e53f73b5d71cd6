import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init). 512 host-platform placeholder devices back both the
single-pod (16x16=256) and multi-pod (2x16x16=512) production meshes.

Per cell:
    lowered  = jit(step, in_shardings, out_shardings).lower(*ShapeDtypeStructs)
    compiled = lowered.compile()
    record memory_analysis(), cost_analysis(), collective schedule (parsed
    from optimized HLO) -> roofline terms (launch/roofline.py).

Usage:
    python -m repro.launch.dryrun --arch smoke-lm --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both --out results/dryrun.json
    python -m repro.launch.dryrun --arch selfjoin --shape syn6d2m --mesh single
"""
import argparse
import dataclasses
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, ShapeCell, all_cells, cell_plan, get_config
from repro.launch import roofline
from repro.launch.mesh import make_production_mesh, make_selfjoin_mesh
from repro.models.lm import LMModel, choose_layout
from repro.train.optimizer import AdamWConfig, adamw_init, opt_state_specs
from repro.train.steps import make_train_step


def _ns(mesh, spec_tree):
    return jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def batch_struct(cfg, cell: ShapeCell):
    B, S = cell.global_batch, cell.seq_len
    if cfg.input_kind == "embeddings":
        return {
            "embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
    return {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }


def batch_specs(cfg, layout):
    b = layout.batch_axes
    if cfg.input_kind == "embeddings":
        return {"embeds": P(b, None, None), "labels": P(b, None)}
    return {"tokens": P(b, None), "labels": P(b, None)}


def opt_config_for(cfg) -> AdamWConfig:
    """Factored v + bf16 m for the 300B+ MoEs (state compression); plain
    AdamW elsewhere. Recorded per arch in EXPERIMENTS.md SDry-run."""
    if cfg.param_count() > 100e9:
        return AdamWConfig(factored=True, m_dtype="bfloat16")
    return AdamWConfig()


def lower_lm_cell(arch: str, cell: ShapeCell, mesh, cfg=None):
    cfg = cfg if cfg is not None else get_config(arch)
    model = LMModel(cfg, mesh)
    pshapes, pspecs = model.abstract_params()
    layout = choose_layout(cfg, mesh, cell.global_batch, cell.seq_len)
    bstruct = batch_struct(cfg, cell)
    bspecs = batch_specs(cfg, layout)

    with mesh:
        if cell.kind == "train":
            ocfg = opt_config_for(cfg)
            oshapes = jax.eval_shape(partial(adamw_init, cfg=ocfg), pshapes)
            ospecs = opt_state_specs(pspecs, ocfg, pshapes)
            step = make_train_step(model, ocfg, param_specs=pspecs)
            fn = jax.jit(
                step,
                in_shardings=(_ns(mesh, pspecs), _ns(mesh, ospecs),
                              _ns(mesh, bspecs)),
                out_shardings=(_ns(mesh, pspecs), _ns(mesh, ospecs), None),
                donate_argnums=(0, 1),
            )
            lowered = fn.lower(pshapes, oshapes, bstruct)
        elif cell.kind == "prefill":
            if cfg.encoder_only:
                fn = jax.jit(
                    lambda p, b: model.encode(p, b, layout),
                    in_shardings=(_ns(mesh, pspecs), _ns(mesh, bspecs)),
                )
                lowered = fn.lower(pshapes, bstruct)
            else:
                cshapes = jax.eval_shape(
                    lambda: model.init_caches(cell.global_batch, cell.seq_len))
                cspecs = model.cache_specs(layout)
                fn = jax.jit(
                    lambda p, b, c: model.prefill(p, b, c, layout),
                    in_shardings=(_ns(mesh, pspecs), _ns(mesh, bspecs),
                                  _ns(mesh, cspecs)),
                    out_shardings=(None, _ns(mesh, cspecs)),
                    donate_argnums=(2,),
                )
                lowered = fn.lower(pshapes, bstruct, cshapes)
        elif cell.kind == "decode":
            cshapes = jax.eval_shape(
                lambda: model.init_caches(cell.global_batch, cell.seq_len))
            cspecs = model.cache_specs(layout)
            tshape = jax.ShapeDtypeStruct((cell.global_batch,), jnp.int32)
            fn = jax.jit(
                lambda p, t, c: model.decode_step(p, t, c, layout),
                in_shardings=(_ns(mesh, pspecs),
                              NamedSharding(mesh, P(layout.batch_axes)),
                              _ns(mesh, cspecs)),
                out_shardings=(None, _ns(mesh, cspecs)),
                donate_argnums=(2,),
            )
            lowered = fn.lower(pshapes, tshape, cshapes)
        else:
            raise ValueError(cell.kind)
    return cfg, layout, lowered


# ---------------------------------------------------------------------------
# Cost probes.
#
# XLA's HloCostAnalysis counts while-loop bodies ONCE (verified on this
# container: a 24-layer scan reports the same flops as its body). Exact
# FLOP/byte totals therefore come from loop-free lowerings: the same cell is
# lowered UNROLLED (cfg.unroll_scans) at L1 = pattern and L2 = 2 x pattern
# layers (pattern = lcm of slstm_every / shared_attn_every so heterogeneous
# stacks stay self-similar), which is exact at those sizes, and extended to
# the full depth with the exactly-linear-in-layers model
#     total(L) = base + (L / pattern) * per_pattern.
# No compile is needed -- lowered.cost_analysis() suffices -- and no mesh:
# FLOPs/bytes are partition-independent (reported per-chip by dividing).
#
# Collectives only exist post-SPMD, so they are extrapolated the same way
# from two COMPILED small-depth lowerings on the real mesh (cheap at L<=16),
# keyed by (kind, bytes, group): count(L) = base + (L/pattern) * per_pattern.
# The full-depth compile (stage A) stays as the shardability/memory proof.
# ---------------------------------------------------------------------------

def _cost_dict(cost) -> dict:
    """Normalize cost_analysis() to a dict (it may be a one-element list)."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost) if cost else {}


def _pattern_len(cfg):
    pat = 1
    if cfg.slstm_every:
        pat = max(pat, cfg.slstm_every)
    if cfg.shared_attn_every:
        pat = max(pat, cfg.shared_attn_every)
    return pat


def _probe_cfg(cfg, n_layers, unroll):
    return dataclasses.replace(cfg, n_layers=n_layers, unroll_scans=unroll)


def _lower_probe(cfg, cell: ShapeCell):
    """Mesh-free lowering of one cell at reduced depth; returns cost dict."""
    model = LMModel(cfg, mesh=None)
    pshapes, _ = model.abstract_params()
    bstruct = batch_struct(cfg, cell)
    if cell.kind == "train":
        ocfg = opt_config_for(cfg)
        oshapes = jax.eval_shape(partial(adamw_init, cfg=ocfg), pshapes)
        step = make_train_step(model, ocfg)
        lowered = jax.jit(step).lower(pshapes, oshapes, bstruct)
    elif cell.kind == "prefill":
        if cfg.encoder_only:
            lowered = jax.jit(model.encode).lower(pshapes, bstruct)
        else:
            cshapes = jax.eval_shape(
                lambda: model.init_caches(cell.global_batch, cell.seq_len))
            lowered = jax.jit(model.prefill).lower(pshapes, bstruct, cshapes)
    else:
        cshapes = jax.eval_shape(
            lambda: model.init_caches(cell.global_batch, cell.seq_len))
        tshape = jax.ShapeDtypeStruct((cell.global_batch,), jnp.int32)
        lowered = jax.jit(model.decode_step).lower(pshapes, tshape, cshapes)
    cost = _cost_dict(lowered.cost_analysis())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0))}


def cost_probe(arch: str, cell: ShapeCell) -> dict:
    """Exact unrolled two-point probe -> whole-program flops/bytes."""
    cfg = get_config(arch)
    pat = _pattern_len(cfg)
    l1, l2 = pat, 2 * pat
    c1 = _lower_probe(_probe_cfg(cfg, l1, True), cell)
    c2 = _lower_probe(_probe_cfg(cfg, l2, True), cell)
    k = (cfg.n_layers - l1) / pat
    out = {}
    for key in ("flops", "bytes"):
        per_pat = c2[key] - c1[key]
        out[key + "_total"] = c1[key] + k * per_pat
        out[key + "_probe"] = (c1[key], c2[key])
    out["probe_layers"] = (l1, l2)
    return out


def _coll_key(c):
    return (c["kind"], c["bytes_result"], c["group_size"], c["cross_pod"])


def _coll_counts(lowered):
    compiled = lowered.compile()
    colls = roofline.parse_collectives(compiled.as_text())
    counts = {}
    for c in colls:
        counts[_coll_key(dataclasses.asdict(c))] = counts.get(
            _coll_key(dataclasses.asdict(c)), 0) + 1
    cost = _cost_dict(compiled.cost_analysis())
    fused = {"flops": float(cost.get("flops", 0.0)),
             "bytes": float(cost.get("bytes accessed", 0.0))}
    return counts, fused


def collective_probe(arch: str, cell: ShapeCell, mesh) -> dict:
    """Two-point compiled probe -> extrapolated collective schedule."""
    cfg = get_config(arch)
    pat = _pattern_len(cfg)
    l1, l2 = pat, 2 * pat
    counts = []
    fused = []
    for lk in (l1, l2):
        cfgk = _probe_cfg(cfg, lk, False)
        _, _, lowered = lower_lm_cell(arch, cell, mesh, cfg=cfgk)
        c, f = _coll_counts(lowered)
        counts.append(c)
        fused.append(f)
    keys = set(counts[0]) | set(counts[1])
    k = (cfg.n_layers - l1) / pat
    # post-fusion per-device bytes/flops, loop-corrected the same way.
    # NOTE: compiled probes keep real chunk sizes, so their while bodies
    # (attn/CE chunk loops) are still counted once -> scale the fused-bytes
    # per-layer delta by the chunk trip count is NOT needed for the linear
    # layer term (each layer body is one loop iteration here at L=1,2 the
    # scan is typically unrolled by XLA); treat as lower-bound companion to
    # the pre-fusion upper bound.
    fused_bytes = fused[0]["bytes"] + k * (fused[1]["bytes"] - fused[0]["bytes"])
    fused_flops = fused[0]["flops"] + k * (fused[1]["flops"] - fused[0]["flops"])
    total_s = 0.0
    wire_total = 0.0
    schedule = []
    for key in sorted(keys, key=str):
        c1, c2 = counts[0].get(key, 0), counts[1].get(key, 0)
        n = max(round(c1 + k * (c2 - c1)), 0)
        kind, bytes_result, g, cross = key
        if kind == "all-reduce":
            wire = 2.0 * bytes_result * (g - 1) / g
        elif kind == "all-gather":
            wire = bytes_result * (g - 1) / g
        elif kind == "reduce-scatter":
            wire = bytes_result * (g - 1)
        elif kind == "all-to-all":
            wire = bytes_result * (g - 1) / g
        else:
            wire = float(bytes_result)
        bw = roofline.DCN_BW if cross else roofline.ICI_BW
        total_s += n * wire / bw
        wire_total += n * wire
        schedule.append({"kind": kind, "bytes": bytes_result, "group": g,
                         "cross_pod": cross, "count": int(n)})
    return {"collective_s": total_s, "wire_bytes_per_device": wire_total,
            "schedule": schedule, "probe_layers": (l1, l2),
            "fused_bytes_per_device": max(fused_bytes, 0.0),
            "fused_flops_per_device": max(fused_flops, 0.0)}


def selfjoin_analytic_cost(cfg, npts, ndims, eps, n_slab, n_model):
    """Analytic per-device flops/bytes for the distributed count step.

    Work model (uniform data in [0,100]^n, the paper's Syn- datasets):
    offsets ~ (3^n+1)/2 (UNICOMP), candidate window C per cell, candidates
    per device per offset = P_cand = P_loc + 2H. Each candidate slot costs
    ~3n flops (sub, mul, add) + compare; gathers dominate bytes.
    """
    p_loc = -(-npts // n_slab)
    halo = max(64, int(p_loc * 0.25))
    p_cand = p_loc + 2 * halo
    n_off = (3 ** ndims + 1) // 2 if cfg.unicomp else 3 ** ndims
    n_off_local = -(-n_off // n_model)
    C = cfg.max_per_cell
    per_slot_flops = 3 * ndims + 2
    flops = p_cand * C * n_off_local * per_slot_flops
    bytes_per_slot = 8 * ndims + 8        # f64 coords + ids/masks
    bytes_ = p_cand * C * n_off_local * bytes_per_slot
    return {"flops_total": flops * n_slab * n_model,
            "bytes_total": bytes_ * n_slab * n_model,
            "flops_per_device": flops, "bytes_per_device": bytes_}


def lower_selfjoin_cell(shape_name: str, mesh):
    from repro.configs.selfjoin import CONFIG, SHAPES as SJ_SHAPES
    from repro.core.distributed import DistJoinConfig, make_distributed_count_step

    by_name = {s[0]: s for s in SJ_SHAPES}
    _, npts, ndims, eps = by_name[shape_name]
    n_slab = mesh.shape["slab"]
    pts_per_dev = -(-npts // n_slab)
    cfg = DistJoinConfig(
        pts_per_device=pts_per_dev,
        n_dims=ndims,
        halo_capacity=max(64, int(pts_per_dev * CONFIG.halo_frac)),
        max_per_cell=CONFIG.max_per_cell,
        unicomp=CONFIG.unicomp,
        model_axis="model",
    )
    step, in_sh = make_distributed_count_step(mesh, cfg)
    coords = jax.ShapeDtypeStruct((n_slab * pts_per_dev, ndims), jnp.float64)
    gids = jax.ShapeDtypeStruct((n_slab * pts_per_dev,), jnp.int32)
    with mesh:
        lowered = step.lower(coords, gids,
                             jax.ShapeDtypeStruct((), jnp.float64))
    return cfg, lowered


def analyze(lowered, cfg, cell, mesh, *, compile_s):
    compiled = lowered.compile()
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)
        }
    except Exception as e:  # CPU backend may not implement it
        mem_info = {"error": str(e)}
    try:
        cost = _cost_dict(compiled.cost_analysis())
    except Exception as e:
        cost = {"error": str(e)}
    chips = mesh.devices.size
    hlo = compiled.as_text()
    summary = roofline.summarize(cost, hlo, chips)
    result = {
        "chips": int(chips),
        "mesh": dict(zip(mesh.axis_names,
                         [int(mesh.shape[a]) for a in mesh.axis_names])),
        "memory_analysis": mem_info,
        "compile_seconds": compile_s,
        "roofline": summary,
    }
    if cell is not None and hasattr(cfg, "active_param_count"):
        result["model_check"] = roofline.model_flops_check(
            cfg, cell, summary["flops_per_device"], chips)
    return result, compiled


def run_cell(arch: str, shape: str, mesh_kind: str, probe_cache: dict):
    """Full dry-run for one cell: stage A (full-depth lower+compile =
    shardability + memory proof), stage B (unrolled cost probe, cached per
    arch|shape), stage C (collective extrapolation probe)."""
    multi = mesh_kind == "multi"
    t0 = time.time()
    if arch == "selfjoin":
        mesh = make_selfjoin_mesh(multi_pod=multi)
        sj_cfg, lowered = lower_selfjoin_cell(shape, mesh)
        cell = None
    else:
        mesh = make_production_mesh(multi_pod=multi)
        cells = {c.name: c for c in SHAPES}
        cell = cells[shape]
        cfg, layout, lowered = lower_lm_cell(arch, cell, mesh)
    lower_s = time.time() - t0
    t0 = time.time()
    result, compiled = analyze(lowered, None if arch == "selfjoin" else cfg,
                               cell, mesh, compile_s=None)
    result["compile_seconds"] = time.time() - t0
    result["lower_seconds"] = lower_s
    chips = mesh.devices.size

    if arch == "selfjoin":
        from repro.configs.selfjoin import SHAPES as SJ_SHAPES
        by_name = {s[0]: s for s in SJ_SHAPES}
        _, npts, ndims, eps = by_name[shape]
        ana = selfjoin_analytic_cost(sj_cfg, npts, ndims, eps,
                                     mesh.shape["slab"], mesh.shape["model"])
        # the step body has no collectives inside its offset scan; the
        # stage-A parse (halo exchange + final psums) is already complete.
        result["roofline"].update(
            flops_per_device=ana["flops_per_device"],
            bytes_per_device=ana["bytes_per_device"],
            compute_s=ana["flops_per_device"] / roofline.PEAK_FLOPS,
            memory_s=ana["bytes_per_device"] / roofline.HBM_BW,
            cost_source="analytic (paper work model); HLO parse for colls",
        )
    else:
        probe_key = f"{arch}|{shape}"
        if probe_key not in probe_cache:
            probe_cache[probe_key] = cost_probe(arch, cell)
        probe = probe_cache[probe_key]
        colls = collective_probe(arch, cell, mesh)
        flops_dev = probe["flops_total"] / chips
        bytes_logical_dev = probe["bytes_total"] / chips   # pre-fusion: upper
        bytes_fused_dev = colls["fused_bytes_per_device"]  # post-fusion: lower
        floor = roofline.traffic_floor(cfg, cell, chips)   # analytic floor
        if cell.kind == "decode":
            # dynamic-update-slice on the KV cache makes HLO byte counts
            # charge the full cache per layer; the analytic model (params +
            # one full cache read + tiny writes) is the faithful estimate.
            bytes_dev = floor
        else:
            bytes_dev = max(bytes_fused_dev, floor)
        r = result["roofline"]
        r.update(
            flops_per_device=flops_dev,
            bytes_per_device=bytes_dev,
            bytes_logical_per_device=bytes_logical_dev,
            bytes_fused_per_device=bytes_fused_dev,
            bytes_floor_per_device=floor,
            compute_s=flops_dev / roofline.PEAK_FLOPS,
            memory_s=bytes_dev / roofline.HBM_BW,
            memory_s_upper=bytes_logical_dev / roofline.HBM_BW,
            collective_s=colls["collective_s"],
            wire_bytes_per_device=colls["wire_bytes_per_device"],
            collective_schedule=colls["schedule"],
            cost_source="flops: unrolled two-point probe (exact at probe "
                         "depths, linear-in-layers); bytes: max(post-fusion "
                         "two-point probe, analytic traffic floor), "
                         "pre-fusion logical bytes kept as upper bound; "
                         "collectives: compiled two-point probe",
            probe=probe,
        )
        r["bottleneck"] = max(
            [("compute", r["compute_s"]), ("memory", r["memory_s"]),
             ("collective", r["collective_s"])], key=lambda kv: kv[1])[0]
        result["model_check"] = roofline.model_flops_check(
            cfg, cell, flops_dev, chips)
        result["layout"] = {
            "batch_axes": str(layout.batch_axes),
            "head_tp": str(layout.head_tp),
            "cache_seq": str(layout.cache_seq),
        }
    # recompute bottleneck for selfjoin too
    r = result["roofline"]
    r["bottleneck"] = max(
        [("compute", r["compute_s"]), ("memory", r["memory_s"]),
         ("collective", r["collective_s"])], key=lambda kv: kv[1])[0]
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    jobs = []
    if args.all:
        for arch, cell, skip in all_cells():
            for mk in meshes:
                jobs.append((arch, cell.name, mk, skip))
        from repro.configs.selfjoin import SHAPES as SJ_SHAPES
        for s in SJ_SHAPES:
            for mk in meshes:
                jobs.append(("selfjoin", s[0], mk, None))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for mk in meshes:
            jobs.append((args.arch, args.shape, mk, None))

    results = {}
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)  # resume support
    probe_cache = results.setdefault("_probe_cache", {})
    for arch, shape, mk, skip in jobs:
        key = f"{arch}|{shape}|{mk}"
        if key in results and "error" not in results[key]:
            print(f"[dryrun] {key}: cached", flush=True)
            continue
        if skip is not None:
            results[key] = {"skipped": skip}
            print(f"[dryrun] {key}: SKIP ({skip})", flush=True)
            continue
        print(f"[dryrun] {key}: lowering...", flush=True)
        t0 = time.time()
        try:
            res = run_cell(arch, shape, mk, probe_cache)
            results[key] = res
            r = res["roofline"]
            print(f"[dryrun] {key}: OK in {time.time()-t0:.1f}s "
                  f"compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
                  f"collective={r['collective_s']:.3e}s "
                  f"bottleneck={r['bottleneck']}", flush=True)
        except Exception as e:
            results[key] = {"error": f"{type(e).__name__}: {e}",
                            "traceback": traceback.format_exc()[-2000:]}
            print(f"[dryrun] {key}: FAIL {type(e).__name__}: {e}", flush=True)
        if args.out:
            tmp = args.out + ".tmp"
            with open(tmp, "w") as f:
                json.dump(results, f, indent=1)
            os.replace(tmp, args.out)
    n_ok = sum(1 for v in results.values() if "roofline" in v)
    n_skip = sum(1 for v in results.values() if "skipped" in v)
    n_err = sum(1 for v in results.values() if "error" in v)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} failed",
          flush=True)
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
