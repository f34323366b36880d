"""Sharding rules: param-path -> PartitionSpec, plus input/cache specs.

Policy (DESIGN.md §5):
 - batch dims ride ("pod","data") (pod axis present only on the multi-pod mesh);
 - TP: head/ff/expert/vocab dims ride "model";
 - FSDP: the complementary big dim of each weight rides "data";
 - an axis is applied only if the dim is divisible by its mesh extent
   (best-effort rule — e.g. smollm's 15 heads stay unsharded on a 16-way TP).
"""
from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def current_mesh():
    """The mesh set by ``jax.set_mesh``, or None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def constrain(x, spec: P):
    """Best-effort with_sharding_constraint: no-op outside a mesh context,
    and silently drops mesh axes that are absent or don't divide the dim
    (e.g. a 15-head tensor on a 16-way model axis stays unsharded)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    entries = [_fit(e, x.shape[i], mesh) for i, e in enumerate(spec)]
    entries += [None] * (x.ndim - len(entries))
    return jax.lax.with_sharding_constraint(x, P(*entries))


BATCH = ("pod", "data")   # generic batch-dim axes (pod dropped on single-pod)

# decode caches with S >= this are sequence-sharded over `model` (shard_map
# partial-softmax decode); smaller caches (local windows, tests) stay
# batch-sharded. MUST stay in sync between cache_specs and the decode paths.
SEQ_SHARD_MIN_S = 8192


def seq_shardable(S: int, mesh) -> bool:
    names = getattr(mesh, "axis_names", ()) if mesh is not None else ()
    return ("model" in names and S % mesh.shape["model"] == 0
            and S >= SEQ_SHARD_MIN_S)


def _fit(spec_entry, dim: int, mesh: Mesh):
    """Drop mesh axes that don't divide `dim`."""
    if spec_entry is None:
        return None
    axes = spec_entry if isinstance(spec_entry, tuple) else (spec_entry,)
    kept = []
    size = 1
    for a in axes:
        if a not in mesh.axis_names:
            continue
        ext = mesh.shape[a]
        if dim % (size * ext) == 0:
            kept.append(a)
            size *= ext
    if not kept:
        return None
    return tuple(kept) if len(kept) > 1 else kept[0]


# path regex -> raw spec (per trailing dims; leading stacked dims get None)
_RULES = [
    (r"embed$",                    ("model", None)),            # [V, D]
    (r"frontend/proj$",            ("data", "model")),
    (r"(w_q|w_uq)$",               ("data", "model")),
    (r"(w_k|w_v)$",                ("data", "model")),
    (r"w_o$",                      ("model", "data")),
    (r"(w_dq|w_dkv)$",             ("data", "model")),
    (r"(w_uk|w_uv)$",              (None, "model")),            # [kv_lora, H*hd]
    (r"(w_gate|w_up|w_in|w_x)$",   ("data", "model")),          # [D, F]
    (r"(w_down|w_out)$",           ("model", "data")),          # [F, D]
    (r"router$",                   ("data", None)),
    (r"ffn/w_gate$",               ("model", "data", None)),    # MoE [E, D, F] (EP)
    (r"ffn/w_up$",                 ("model", "data", None)),
    (r"ffn/w_down$",               ("model", None, "data")),
    (r"w_bcdt$",                   ("model", None)),            # [d_inner, ...]
    (r"w_dt$",                     (None, "model")),
    (r"(conv_w|conv_b|dt_bias|D)$", (None,)),
    (r"log_neg_A$",                ("model", None)),
    (r"(w_a|w_i)$",                ("model", None)),            # lru [W, W]
    (r"(norm|scale|bias|log_lambda|q_norm|k_norm|kv_norm)", (None,)),
]
# NOTE: order matters — first match wins; MoE expert weights are matched by
# the `ffn/...` entries *before* the generic w_gate/w_down rules because the
# generic rules assume 2-D weights; see _spec_for.


def _spec_for(path: str, ndim: int, mesh: Mesh, shape) -> P:
    raw: tuple | None = None
    # 3-D (stacked-expert) weights need the MoE rules; check those first.
    for pat, spec in _RULES:
        if pat.startswith("ffn/") and re.search(pat, path) and ndim - _lead(path) == 3:
            raw = spec
            break
    if raw is None:
        for pat, spec in _RULES:
            if re.search(pat, path):
                raw = spec
                break
    if raw is None:
        raw = (None,) * ndim
    lead = ndim - len(raw)
    if lead < 0:          # param has fewer dims than rule (e.g. reduced cfg)
        raw = raw[-ndim:]
        lead = 0
    entries = [None] * lead + [
        _fit(s, shape[lead + i], mesh) for i, s in enumerate(raw)]
    return P(*entries)


def _lead(path: str) -> int:
    # stacked group params have 1 leading layer dim
    return 1 if "/groups/" in path or path.startswith("groups/") else 0


def _path_str(keypath) -> str:
    parts = []
    for k in keypath:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_specs(params, mesh: Mesh, *, profile: str = "train"):
    """PartitionSpec pytree matching `params`.

    profile="train": TP over `model` + FSDP over `data` (weights gathered
    per layer, reduce-scattered grads) — the memory-optimal training layout.
    profile="serve": TP/EP only — weights replicated across `data`; decoding
    must NOT re-gather FSDP shards every token (§Perf iteration S1)."""
    def one(kp, leaf):
        path = _path_str(kp)
        nd, shape = np.ndim(leaf), np.shape(leaf)
        if profile == "serve" and re.search(r"ffn/(w_gate|w_up|w_down)$", path) \
                and nd - _lead(path) == 3:
            # serving MoE layout: EP over `data`, intra-expert TP over
            # `model` — every expert shard lives on exactly one device row,
            # nothing is re-gathered per decode step.
            lead = [None] * _lead(path)
            if path.endswith("w_down"):       # [L, E, F, D]
                return P(*lead, _fit("data", shape[-3], mesh),
                         _fit("model", shape[-2], mesh), None)
            return P(*lead, _fit("data", shape[-3], mesh), None,
                     _fit("model", shape[-1], mesh))
        spec = _spec_for(path, nd, mesh, shape)
        if profile == "serve":
            spec = P(*[_strip_data(e) for e in spec])
        return spec
    return jax.tree_util.tree_map_with_path(one, params)


def _strip_data(entry):
    if entry is None:
        return None
    axes = entry if isinstance(entry, tuple) else (entry,)
    kept = tuple(a for a in axes if a not in ("data", "pod"))
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def param_shardings(params, mesh: Mesh, *, profile: str = "train"):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params, mesh, profile=profile))


def opt_state_specs(opt_state, mesh: Mesh):
    """Optimizer-state specs: moments shard like their params (path-based
    rules still match since state paths embed the param name); adafactor's
    factored stats drop the reduced dim's entry; `count` is replicated."""
    def one(kp, leaf):
        path = _path_str(kp)
        nd = np.ndim(leaf)
        if path.endswith("count"):
            return P()
        if path.endswith("/vr"):          # mean over last dim of the param
            s = _spec_for(path[:-3], nd + 1, mesh, np.shape(leaf) + (10 ** 9,))
            return P(*s[:-1])
        if path.endswith("/vc"):          # mean over second-to-last dim
            shape = np.shape(leaf)
            fake = shape[:-1] + (10 ** 9,) + shape[-1:]
            s = _spec_for(path[:-3], nd + 1, mesh, fake)
            return P(*(s[:-2] + s[-1:]))
        return _spec_for(path, nd, mesh, np.shape(leaf))
    return jax.tree_util.tree_map_with_path(one, opt_state)


# ----------------------------------------------------------- activations/io
def data_spec(mesh: Mesh, ndim: int) -> P:
    """[B, ...] batch-sharded."""
    return P(batch_axes(mesh), *([None] * (ndim - 1)))


def cache_specs(cache, mesh: Mesh):
    """KV/state caches: batch dim sharded over (pod,data) — best-effort (the
    long_500k cell has B=1 and falls back toward replication); for attention
    KV [n,B,S,K,hd] the kv-head dim rides model when divisible; MLA latent
    [n,B,S,latent] is batch-only (no head dim — the paper's scenario)."""
    b = batch_axes(mesh)

    def one(kp, leaf):
        nd = np.ndim(leaf)
        shape = np.shape(leaf)
        bfit = _fit(b, shape[1], mesh) if nd >= 2 else None
        if nd == 5:       # [n, B, S, K, hd]
            # big full-attention caches are S-sharded over model (matches
            # core.etap.seq_sharded_gqa_decode); small (window) caches are
            # batch-sharded only.
            s = "model" if seq_shardable(shape[2], mesh) else None
            return P(None, bfit, s, None, None)
        if nd == 4:       # [n, B, S, latent] or [n, B, d_inner, N]
            path = _path_str(kp)
            if path.endswith("h"):           # mamba state [n,B,d_inner,N]
                d = _fit("model", shape[2], mesh)
                return P(None, bfit, d, None)
            # MLA latent cache: S-sharded over model (no head dim exists);
            # matches core.etap.seq_sharded_decode's in_specs.
            s = "model" if seq_shardable(shape[2], mesh) else None
            return P(None, bfit, s, None)
        if nd == 3:       # [n, B, W] / [n, B, k-1(, ...)]
            d = _fit("model", shape[2], mesh)
            return P(None, bfit, d)
        return P(*([None] * nd))
    return jax.tree_util.tree_map_with_path(one, cache)
