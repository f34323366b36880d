"""Roofline-term extraction from compiled dry-run artifacts (DESIGN.md §7).

Peaks are per chip and keyed by ``jax.Device.device_kind`` (:data:`PEAKS`);
a device kind missing from the table is an error, never a default.  The
dry-run and the split-KV model project onto a described v5e (:data:`V5E`),
since that is the chip the production mesh is built from.

The SPMD-partitioned HLO module is the *per-device* program, so
cost_analysis() FLOPs/bytes are per-chip already:
    compute term    = HLO_FLOPs / peak
    memory term     = HLO_bytes / HBM_bw
    collective term = collective_bytes / link_bw
collective_bytes is parsed from the HLO text: the result-shape bytes of each
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field


@dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks."""
    flops: float      # dense bf16 FLOP/s
    hbm_bw: float     # HBM bytes/s
    ici_bw: float     # bytes/s per ICI link
    source: str


V5E = "TPU v5 lite"   # what jax reports as a v5e chip's device_kind

PEAKS = {
    V5E: Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
               source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                      "bf16, 819 GB/s HBM, 1,600 Gbit/s ICI over 4 links"),
}


def peaks(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to roofline.PEAKS with their source") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# shapes like bf16[16,512]{1,0} or f32[] ; tuples handled by re-scanning
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_by_kind: dict = field(default_factory=dict)
    count_by_kind: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum result-shape bytes of every collective op in (per-device) HLO."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(\(?.*?\)?)\s+([\w\-]+)\(", ls)
        if not m:
            continue
        op = m.group(2)
        kind = next((c for c in _COLLECTIVES
                     if op == c or op.startswith(c + ".")), None)
        if kind is None:
            continue
        b = _shape_bytes(m.group(1))
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + b
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
    return stats


def roofline_terms_from_analysis(analysis: dict) -> dict:
    """Terms from hlo_analysis.analyze() (trip-count-aware — the primary
    source; cost_analysis() counts while bodies once and is kept only as a
    cross-check column)."""
    pk = PEAKS[V5E]
    flops = float(analysis["flops"])
    byts = float(analysis["bytes"])
    coll = float(analysis["collective_bytes"])
    out = {
        "hlo_flops": flops,
        "hlo_bytes": byts,
        "collective_bytes": coll,
        "t_compute": flops / pk.flops,
        "t_memory": byts / pk.hbm_bw,
        "t_collective": coll / pk.ici_bw,
    }
    dom = max(("t_compute", "t_memory", "t_collective"), key=lambda k: out[k])
    out["bottleneck"] = dom[2:]
    t_total = max(out["t_compute"], out["t_memory"], out["t_collective"])
    out["roofline_fraction"] = out["t_compute"] / t_total if t_total > 0 else 0.0
    return out


def roofline_terms(cost: dict, coll: CollectiveStats) -> dict:
    pk = PEAKS[V5E]
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    out = {
        "hlo_flops": flops,
        "hlo_bytes": byts,
        "collective_bytes": float(coll.total_bytes),
        "t_compute": flops / pk.flops,
        "t_memory": byts / pk.hbm_bw,
        "t_collective": coll.total_bytes / pk.ici_bw,
    }
    dom = max(("t_compute", "t_memory", "t_collective"), key=lambda k: out[k])
    out["bottleneck"] = dom[2:]
    t_total = max(out["t_compute"], out["t_memory"], out["t_collective"])
    out["roofline_fraction"] = out["t_compute"] / t_total if t_total > 0 else 0.0
    return out


def splitkv_roofline(BG: int, S: int, H: int, Dk: int, Dv: int,
                     n_splits: int, *, kv_itemsize: int = 2,
                     mla_fused: bool = False) -> dict:
    """Roofline terms for the two-phase split-KV decode pipeline
    (DESIGN.md §3): phase 1 streams the KV cache once and writes per-split
    fp32 (m, ℓ, Accᵀ) stats; phase 2 re-reads the stats and writes O.

    The split count buys parallelism (occupancy factor on the compute term)
    and pays for it in stat traffic — the scheduler's STATS_TRAFFIC_BUDGET
    cap is exactly the requirement that `overhead` stays ≪ 1 here."""
    from repro.kernels.etap.schedule import DEFAULT_CORES

    pk = PEAKS[V5E]
    q_bytes = BG * H * Dk * kv_itemsize
    kv_bytes = BG * S * (Dk if mla_fused else Dk + Dv) * kv_itemsize
    stat_bytes = BG * n_splits * (2 * H + Dv * H) * 4
    o_bytes = BG * H * Dv * kv_itemsize
    flops = 2.0 * BG * S * H * (Dk + Dv)

    occupancy = min(1.0, BG * n_splits / DEFAULT_CORES)
    t_partial_mem = (q_bytes + kv_bytes + stat_bytes) / pk.hbm_bw
    t_partial_compute = flops / (pk.flops * occupancy)
    t_combine = (stat_bytes + o_bytes) / pk.hbm_bw
    t_total = max(t_partial_mem, t_partial_compute) + t_combine
    return {
        "kv_bytes": kv_bytes,
        "stat_bytes": stat_bytes,
        "t_partial_mem": t_partial_mem,
        "t_partial_compute": t_partial_compute,
        "t_combine": t_combine,
        "t_total": t_total,
        "occupancy": occupancy,
        "overhead": (2 * stat_bytes + o_bytes) / max(kv_bytes, 1),
        "bottleneck": ("memory" if t_partial_mem >= t_partial_compute
                       else "compute"),
    }


def model_flops(cfg, cell, n_active_params: int) -> float:
    """6·N·D (train) / 2·N·D (inference fwd) convention, attention excluded.
    decode processes global_batch tokens; train/prefill B·S tokens."""
    tokens = cell.global_batch * (1 if cell.is_decode else cell.seq_len)
    mult = 6.0 if cell.kind == "train" else 2.0
    return mult * n_active_params * tokens


def active_params(cfg) -> int:
    """Approximate activated parameter count (MoE: top_k of num_experts +
    shared expert; embeddings counted once)."""
    D, V, L = cfg.d_model, cfg.vocab_size, cfg.num_layers
    hd = cfg.resolved_head_dim
    total = V * D
    kinds = cfg.layer_kinds()
    for i, kind in enumerate(kinds):
        if kind == "attn":
            if cfg.attention_kind == "mla":
                m = cfg.mla
                total += D * m.q_lora_rank + m.q_lora_rank * cfg.num_heads * m.qk_head_dim
                total += D * (m.kv_lora_rank + m.qk_rope_head_dim)
                total += m.kv_lora_rank * cfg.num_heads * (m.qk_nope_head_dim + m.v_head_dim)
                total += cfg.num_heads * m.v_head_dim * D
            else:
                total += D * hd * (cfg.num_heads + 2 * cfg.num_kv_heads) \
                    + cfg.num_heads * hd * D
        elif kind == "rglru":
            W = cfg.lru_width or D
            total += 2 * D * W + 2 * W * W + W * D
        elif kind == "ssm":
            di = cfg.ssm.expand * D
            dtr = cfg.ssm.dt_rank or -(-D // 16)
            total += 2 * D * di + di * (dtr + 2 * cfg.ssm.d_state) \
                + dtr * di + di * D
        if kind == "ssm":
            continue
        if cfg.moe_layer(i):
            F = cfg.moe.d_ff_expert or cfg.d_ff
            total += cfg.moe.top_k * 3 * D * F          # activated experts
            if cfg.moe.shared_expert:
                total += 3 * D * F
        elif kind in ("attn", "rglru"):
            total += 3 * D * cfg.d_ff
    return int(total)


def total_params(cfg) -> int:
    """Full parameter count (MoE: all experts)."""
    act = active_params(cfg)
    if cfg.moe is None:
        return act
    F = cfg.moe.d_ff_expert or cfg.d_ff
    n_moe = sum(1 for i in range(cfg.num_layers) if cfg.moe_layer(i))
    extra = n_moe * (cfg.moe.num_experts - cfg.moe.top_k) * 3 * cfg.d_model * F
    return act + extra
