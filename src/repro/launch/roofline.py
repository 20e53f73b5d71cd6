"""Roofline analysis from compiled dry-run artifacts (no hardware needed).

Three terms, in seconds per step per chip (TPU v5e constants as assigned):

    compute    = HLO_FLOPs / (chips * 197e12)          [bf16 peak]
    memory     = HLO_bytes / (chips * 819e9)           [HBM]
    collective = sum(bytes_on_wire_per_device) / link_bw per collective

FLOPs/bytes come from ``compiled.cost_analysis()`` (totals for the whole
SPMD program: already per-device in XLA's SPMD view -- see note below).
Collective traffic is NOT in cost_analysis, so we parse the optimized HLO
(``compiled.as_text()``) and apply ring-model byte counts:

    all-reduce          2 * S * (g-1)/g      (S = result bytes per device)
    all-gather          S_out * (g-1)/g      (receives everyone else's shard)
    reduce-scatter      S_in * (g-1)/g
    all-to-all          S * (g-1)/g
    collective-permute  S                    (single hop)

Cross-pod groups (device ids spanning >1 block of 256) ride DCN
(25 GB/s assumed) instead of ICI (50 GB/s per the assignment).

NOTE on cost_analysis semantics: for an SPMD-partitioned program, XLA reports
the per-partition (per-device) op set, so flops/bytes are per device; we
multiply by ``chips`` only where a global number is reported (detected via
the program's num_partitions).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
ICI_BW = 50e9              # bytes/s / link
DCN_BW = 25e9              # bytes/s cross-pod (assumed)
POD_SIZE = 256
VMEM_BYTES = 128 * 2 ** 20  # v5e VMEM per core; the fused kernel's budget


def fused_join_vmem_bytes(*, c: int, tq: int, np_pad: int = 8,
                          dtype_bytes: int = 4,
                          run_loop: bool = False) -> int:
    """Static VMEM footprint of one fused-join grid step (bytes).

    Mirrors the block/scratch shapes of ``kernels.fused_join
    ._fused_join_hits_pallas``: the pipelined blocks -- query tile
    (tq, np_pad), hits (1, tq, c) int8, counts (tq, 1) int32, the eps
    scalar -- are counted TWICE (Pallas double-buffers revolving in/out
    blocks across grid steps), plus the scratch: the explicitly
    double-buffered (2, c, 128) window (the kernel's DMA moves full
    128-lane rows) and the (tq, c) int32 hit rows. The (1, tq) SMEM
    descriptor blocks are excluded. The contract prover
    (analysis/contracts.py C6) checks every (class, tile) the occupancy
    plan can launch against ``VMEM_BYTES``.

    ``run_loop`` (the cell-run DMA dedup, DESIGN.md S11) does NOT change
    the footprint: the run plan's ``run_ord`` descriptor rides in SMEM
    like win_start/win_count, and the kernel keeps the same two window
    slots -- only the start/wait SCHEDULE changes (per run instead of per
    row). The parameter exists so provers state the mode they checked.
    """
    from repro.kernels.fused_join import LANES

    del run_loop   # same slots, same blocks; see docstring
    blocks = (tq * np_pad * dtype_bytes   # query tile
              + tq * c                    # int8 hits block
              + tq * 4                    # counts
              + dtype_bytes)              # eps2
    scratch = 2 * c * LANES * dtype_bytes + tq * c * 4
    return 2 * blocks + scratch

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0,
}

_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

# e.g.:  %all-gather.7 = bf16[16,4096,1024]{2,1,0} all-gather(...)
_OP_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^\s]*\s+"
    r"(all-reduce-start|all-reduce|all-gather-start|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"\(")
# tuple-result collectives: (bf16[...], bf16[...]) all-reduce(...)
_TUPLE_RE = re.compile(
    r"=\s+\(([^)]*)\)\s+"
    r"(all-reduce-start|all-reduce|all-gather-start|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[(\d+)\]")
_PERMUTE_PAIRS_RE = re.compile(r"source_target_pairs=\{\{")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclasses.dataclass
class Collective:
    kind: str
    bytes_result: int
    group_size: int
    cross_pod: bool
    wire_bytes: float      # per device
    seconds: float


def _group_info(line: str):
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        n_groups, group_size, total = map(int, m.groups())
        # iota groups [G,S]<=[N]: contiguity depends on the transpose spec;
        # conservatively flag cross-pod when a group must span >1 pod block.
        cross = group_size > POD_SIZE or (
            "T(" in line and total > POD_SIZE)
        return group_size, cross
    m = _GROUPS_RE.search(line)
    if m:
        ids = [int(x) for x in m.group(1).split(",")]
        cross = len({i // POD_SIZE for i in ids}) > 1
        return len(ids), cross
    return 1, False


def parse_collectives(hlo_text: str):
    """Collective ops with ring-model per-device wire bytes and time."""
    out = []
    for line in hlo_text.splitlines():
        line = line.strip()
        mt = _TUPLE_RE.search(line)
        mo = _OP_RE.search(line) if mt is None else None
        if mt is None and mo is None:
            continue
        if "-done" in line:
            continue
        if mt is not None:
            kind = mt.group(2)
            bytes_result = sum(_shape_bytes(d, s)
                               for d, s in _SHAPE_RE.findall(mt.group(1)))
        else:
            kind = mo.group(3)
            bytes_result = _shape_bytes(mo.group(1), mo.group(2))
        kind = kind.replace("-start", "")
        g, cross = _group_info(line)
        if g <= 1 and kind != "collective-permute":
            continue
        if kind == "all-reduce":
            wire = 2.0 * bytes_result * (g - 1) / g
        elif kind == "all-gather":
            wire = bytes_result * (g - 1) / g
        elif kind == "reduce-scatter":
            wire = bytes_result * (g - 1)       # result is the scattered shard
        elif kind == "all-to-all":
            wire = bytes_result * (g - 1) / g
        else:  # collective-permute
            wire = float(bytes_result)
        bw = DCN_BW if cross else ICI_BW
        out.append(Collective(kind, bytes_result, g, cross, wire, wire / bw))
    return out


def _loop_trip_counts(hlo_text: str) -> float:
    """Best-effort: collectives inside while loops execute trip_count times.

    XLA CPU emits scan as while; cost_analysis already multiplies flops by
    trip counts, but our HLO text parse sees the loop body once. We extract
    known trip counts and scale collectives found inside loop bodies.
    (Approximation: a single dominant scan-over-layers loop.)
    """
    m = re.findall(r"trip_count=(\d+)", hlo_text)
    return max((int(x) for x in m), default=1)


def summarize(cost: dict, hlo_text: str, chips: int, *,
              scale_loop_collectives: bool = True) -> dict:
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    colls = parse_collectives(hlo_text)
    trip = _loop_trip_counts(hlo_text) if scale_loop_collectives else 1

    # Group collectives by whether they appear before or inside loops is
    # brittle from text; we scale all by the dominant trip count when the
    # program has a scan (documented approximation, see module docstring).
    wire = sum(c.wire_bytes for c in colls)
    coll_s = sum(c.seconds for c in colls)
    body_count = len(colls)

    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_accessed / HBM_BW
    return {
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "collectives": [dataclasses.asdict(c) for c in colls],
        "n_collectives": body_count,
        "loop_trip_count": trip,
        "wire_bytes_per_device": wire,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": coll_s,
        "bottleneck": max(
            [("compute", compute_s), ("memory", memory_s),
             ("collective", coll_s)], key=lambda kv: kv[1])[0],
    }


def traffic_floor(cfg, cell, chips: int) -> float:
    """Analytic lower bound on HBM bytes/device/step.

    Used to floor the post-fusion HLO byte estimate (whose while-loop bodies
    are counted once). Terms: parameter reads (3x for train: fwd, remat-fwd,
    bwd), gradient + optimizer-state traffic (train), KV/SSM cache traffic
    (decode/prefill), boundary activations (train, remat).
    """
    P = cfg.param_count()
    PA = cfg.active_param_count()
    bf16 = 2
    B, S = cell.global_batch, cell.seq_len
    if cell.kind == "train":
        act = cfg.n_layers * B * S * cfg.d_model * bf16 * 2   # save + reload
        opt = 2 * (4 + 4 + 4) * P                             # m/v/master r+w
        total = (3 * bf16 + 2 * bf16) * P + opt + act
    elif cell.kind == "prefill":
        cache = 2 * B * S * cfg.n_kv_heads * cfg.head_dim * cfg.n_layers * bf16
        act = cfg.n_layers * B * S * cfg.d_model * bf16
        total = bf16 * P + cache + act
    else:  # decode
        touched = min(1.0, B * max(cfg.top_k, 1) / max(cfg.n_experts, 1)) \
            if cfg.n_experts else 1.0
        params = bf16 * (PA + touched * (P - PA))
        cache = 0.0
        if cfg.family in ("dense", "moe", "vlm"):
            cache = 2 * B * S * cfg.n_kv_heads * cfg.head_dim * cfg.n_layers * bf16
        elif cfg.family == "hybrid":
            n_inv = -(-cfg.n_layers // cfg.shared_attn_every) \
                if cfg.shared_attn_every else 0
            cache = 2 * B * S * cfg.n_kv_heads * cfg.head_dim * n_inv * bf16
            H = cfg.d_inner // cfg.ssm_head_dim
            cache += 2 * B * H * cfg.ssm_state * cfg.ssm_head_dim * 4 * cfg.n_layers
        elif cfg.family == "ssm":
            dh = cfg.d_inner // cfg.n_heads
            cache = 2 * B * cfg.n_heads * dh * dh * 4 * cfg.n_layers
        total = params + cache
    return total / chips


def model_flops_check(cfg, cell, hlo_flops_per_device: float, chips: int):
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D; ratio vs compiled FLOPs."""
    n = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        model_flops = 6.0 * n * tokens
    elif cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        model_flops = 2.0 * n * tokens
    else:  # decode: one token per sequence
        tokens = cell.global_batch
        model_flops = 2.0 * n * tokens
    hlo_total = hlo_flops_per_device * chips
    return {
        "model_flops": model_flops,
        "hlo_flops_total": hlo_total,
        "useful_fraction": model_flops / hlo_total if hlo_total else 0.0,
    }
