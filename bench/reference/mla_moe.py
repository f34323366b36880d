"""Plain float32 reference of the DeepSeek-V3-type stack the benchmark
serves: MLA in its decompressed form, dense GLU FFN, MoE with a shared
expert, tied output head.  No cache, no kernels, no batching.

It follows the configuration as the repository defines it, with these
departures from the published models (stated in each configuration file):
softmax top-k routing over the experts held here, without the published
sigmoid group-limited routing and its scaling factor; plain RoPE without
YaRN; the output head tied to the embedding.

The weights are made again from the seed by the same random draws as the
program's initialiser (a key tree of ``jax.random.split`` and normal draws
rounded to the serving dtype), layer by layer, so nothing the program made
is taken.  Matrix products run at ``precision="highest"``; ``mode="fp8"``
rounds the inputs of every product to float8_e4m3fn with absmax scaling,
the precision below the configuration's bfloat16 (the control).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = 448.0


# ------------------------------------------------------------- weights
def _groups(arch: dict) -> list:
    """Runs of equal layer kinds, in order: [(is_moe, n), ...]."""
    runs = []
    for m in arch["moe_layers"]:
        if runs and runs[-1][0] == m:
            runs[-1][1] += 1
        else:
            runs.append([m, 1])
    if len(runs) > 4:
        raise ValueError("layer pattern with more than 4 runs")
    return [(m, n) for m, n in runs]


def _draw(key, shape, std, dtype):
    w = jax.random.normal(key, shape, F32) * std
    return w.astype(dtype).astype(F32)


def layer_keys(seed: int, arch: dict):
    """(embedding key, [per-layer block key]) of the key tree."""
    groups = _groups(arch)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(groups) + 2)
    blocks = []
    for g, (_, n) in enumerate(groups):
        for k in jax.random.split(keys[2 + g], n):
            blocks.append(jax.random.split(k, 1)[0])
    return keys[0], blocks


def embed_weights(key, arch: dict):
    dt = jnp.dtype(arch["dtype"])
    return _draw(key, (arch["vocab"], arch["d_model"]), 0.02, dt)


def _mlp(key, d, f, dt):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": _draw(k1, (d, f), d ** -0.5, dt),
            "w_up": _draw(k2, (d, f), d ** -0.5, dt),
            "w_down": _draw(k3, (f, d), f ** -0.5, dt)}


def layer_weights(key, arch: dict, is_moe: bool) -> dict:
    """One layer's weights, drawn op by op as the initialiser draws them
    (a fused program may round the normal transform differently)."""
    dt = jnp.dtype(arch["dtype"])
    D, H = arch["d_model"], arch["heads"]
    qr, kvr = arch["q_lora_rank"], arch["kv_lora_rank"]
    nope, rope, dv = arch["qk_nope"], arch["qk_rope"], arch["v_head"]
    k1, k2, _, _ = jax.random.split(key, 4)
    ks = jax.random.split(k1, 7)
    w = {"w_dq": _draw(ks[0], (D, qr), D ** -0.5, dt),
         "w_uq": _draw(ks[1], (qr, H * (nope + rope)), qr ** -0.5, dt),
         "w_dkv": _draw(ks[2], (D, kvr + rope), D ** -0.5, dt),
         "w_uk": _draw(ks[3], (kvr, H * nope), kvr ** -0.5, dt),
         "w_uv": _draw(ks[4], (kvr, H * dv), kvr ** -0.5, dt),
         "w_o": _draw(ks[5], (H * dv, D), (H * dv) ** -0.5, dt)}
    if not is_moe:
        w["ffn"] = _mlp(k2, D, arch["d_ff"], dt)
        return w
    E, F = arch["experts"], arch["d_ff_expert"]
    ks = jax.random.split(k2, 5)
    w["router"] = jax.random.normal(ks[0], (D, E), F32) * D ** -0.5
    w["experts"] = {"w_gate": _draw(ks[1], (E, D, F), D ** -0.5, dt),
                    "w_up": _draw(ks[2], (E, D, F), D ** -0.5, dt),
                    "w_down": _draw(ks[3], (E, F, D), F ** -0.5, dt)}
    if arch["shared_experts"]:
        w["shared"] = _mlp(ks[4], D, F, dt)
    return w


def _items(arch: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in arch.items()
                        if not isinstance(v, list)))


# ----------------------------------------------------------- arithmetic
def _q8(x, axis):
    """Round to float8_e4m3fn with absmax scaling along ``axis`` (the
    contraction axis), back in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _ein(spec: str, a, b, mode: str):
    """einsum at highest precision; in fp8 mode both inputs are rounded
    along their contracted axes first."""
    if mode == "fp8":
        ins, out = spec.split("->")
        sa, sb = ins.split(",")
        red = set(sa) & set(sb) - set(out)
        a = _q8(a, tuple(i for i, c in enumerate(sa) if c in red))
        b = _q8(b, tuple(i for i, c in enumerate(sb) if c in red))
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """Neox-style rotation of the last axis; x [S, ..., d], pos [S]."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    if x.ndim == 3:
        sin, cos = sin[:, None, :], cos[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _glu(w, x, mode):
    h = jax.nn.silu(_ein("sd,df->sf", x, w["w_gate"], mode)) \
        * _ein("sd,df->sf", x, w["w_up"], mode)
    return _ein("sf,fd->sd", h, w["w_down"], mode)


@partial(jax.jit, static_argnums=(3, 4))
def _pre(w, x, pos, arch_items, mode):
    """Rows x [R, D] at positions pos -> queries [R, H, nope+rope] and
    latent rows [R, kvr+rope] (normed c_kv, rotated k_rope)."""
    a = dict(arch_items)
    H, nope, kvr = a["heads"], a["qk_nope"], a["kv_lora_rank"]
    h = _rms(x, a["norm_eps"])
    cq = _rms(_ein("sd,dq->sq", h, w["w_dq"], mode), a["norm_eps"])
    q = _ein("sq,qf->sf", cq, w["w_uq"], mode).reshape(x.shape[0], H, -1)
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], pos, a["rope_theta"])], -1)
    dkv = _ein("sd,dc->sc", h, w["w_dkv"], mode)
    c = jnp.concatenate([_rms(dkv[:, :kvr], a["norm_eps"]),
                         _rope(dkv[:, kvr:], pos, a["rope_theta"])], -1)
    return q, c


@partial(jax.jit, static_argnums=(2, 3))
def _kv(w, c, arch_items, mode):
    """Latent rows [K, kvr+rope] -> decompressed keys [K, H, nope+rope]
    and values [K, H, dv]."""
    a = dict(arch_items)
    H, kvr = a["heads"], a["kv_lora_rank"]
    ckv, kr = c[:, :kvr], c[:, kvr:]
    k_nope = _ein("sc,cf->sf", ckv, w["w_uk"], mode).reshape(c.shape[0], H,
                                                              -1)
    v = _ein("sc,cf->sf", ckv, w["w_uv"], mode).reshape(c.shape[0], H, -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        kr[:, None, :], (c.shape[0], H, kr.shape[-1]))], -1)
    return k, v


@partial(jax.jit, static_argnums=(5,))
def _attend(q, qpos, k, v, kpos, mode):
    """Causal softmax attention of query rows over keys at kpos <= qpos
    (padded keys carry a position past every query)."""
    scale = q.shape[-1] ** -0.5
    s = _ein("qhd,khd->hqk", q, k, mode) * scale
    s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _ein("hqk,khd->qhd", p, v, mode)
    return o.reshape(q.shape[0], -1)


@partial(jax.jit, static_argnums=(3, 4, 5))
def _post(w, x, o, arch_items, is_moe, mode):
    """Residual adds of the attention output and of the FFN."""
    a = dict(arch_items)
    x = x + _ein("sf,fd->sd", o, w["w_o"], mode)
    h = _rms(x, a["norm_eps"])
    if not is_moe:
        return x + _glu(w["ffn"], h, mode)
    gates = jax.nn.softmax(_ein("sd,de->se", h, w["router"], mode), -1)
    top, idx = jax.lax.top_k(gates, a["top_k"])
    g = jnp.zeros_like(gates).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)
    ex = w["experts"]
    y = jnp.zeros_like(x)
    for e in range(a["experts"]):
        we = {k: ex[k][e] for k in ("w_gate", "w_up", "w_down")}
        y = y + g[:, e:e + 1] * _glu(we, h, mode)
    if "shared" in w:
        y = y + _glu(w["shared"], h, mode)
    return x + y


@partial(jax.jit, static_argnums=(2, 3))
def _head(x, emb, eps, mode):
    return _ein("sd,vd->sv", _rms(x, eps), emb, mode)


# -------------------------------------------------------------- forward
def _pad_rows(x, n):
    pad = n - x.shape[0]
    return x if pad == 0 else jnp.concatenate(
        [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])


def _common_prefix(seqs: list) -> int:
    """Length of the longest common prefix, leaving every sequence at
    least one row of its own."""
    P = min(len(s) for s in seqs) - 1
    for s in seqs[1:]:
        neq = np.nonzero(s[:P] != seqs[0][:P])[0]
        P = int(neq[0]) if neq.size else P
    return P


def logits(arch: dict, seed: int, seqs: list, rows: list, *,
           mode: str = "fp32", block: int = 512, key_align: int = 4096):
    """Reference logits of ``seqs`` (int token arrays) at ``rows`` (per
    sequence, the positions whose next-token logits are wanted).

    The sequences' longest common prefix is computed once and every
    sequence's remaining rows attend to it: causal attention makes that
    the same arithmetic as running each sequence alone.  Layers run one at
    a time, their weights made from the seed and dropped after use; rows
    go in blocks of ``block``, keys are padded to a multiple of
    ``key_align``, so the compiled shapes repeat across calls.
    Returns a list of float32 numpy arrays [len(rows[i]), vocab]."""
    seqs = [np.asarray(s, np.int32) for s in seqs]
    P = _common_prefix(seqs)
    # segment 0 is the shared prefix (possibly empty), 1.. the own rows
    segs = [seqs[0][:P]] + [s[P:] for s in seqs]
    starts = [0] + [P] * len(seqs)
    kpad = -(-max(len(s) for s in seqs) // key_align) * key_align
    items = _items(arch)
    emb_key, block_keys = layer_keys(seed, arch)
    emb = embed_weights(emb_key, arch)

    def padded(n):
        return -(-n // block) * block

    xs = [_pad_rows(jnp.take(emb, jnp.asarray(t), axis=0), padded(len(t)))
          for t in segs]
    pos = [jnp.asarray(_pad_rows(np.arange(s0, s0 + len(t), dtype=np.int32),
                                 padded(len(t))))
           for t, s0 in zip(segs, starts, strict=True)]
    with jax.default_matmul_precision("highest"):
        for layer, key in enumerate(block_keys):
            is_moe = bool(arch["moe_layers"][layer])
            w = layer_weights(key, arch, is_moe)
            kv = []
            for x, p, t in zip(xs, pos, segs, strict=True):
                if len(t) == 0:
                    kv.append(None)
                    continue
                c = jnp.concatenate([
                    _pre(w, x[i:i + block], p[i:i + block], items, mode)[1]
                    for i in range(0, x.shape[0], block)])
                k, v = _kv(w, c, items, mode)
                kv.append((k[:len(t)], v[:len(t)]))
            new = []
            for j, (x, p, t) in enumerate(zip(xs, pos, segs, strict=True)):
                if len(t) == 0:
                    new.append(x)
                    continue
                parts = [kv[0]] if j > 0 and P > 0 else []
                parts.append(kv[j])
                k = _pad_rows(jnp.concatenate([a for a, _ in parts]), kpad)
                v = _pad_rows(jnp.concatenate([b for _, b in parts]), kpad)
                nk = starts[j] + len(t)
                kpos = jnp.where(jnp.arange(kpad) < nk, jnp.arange(kpad),
                                 np.iinfo(np.int32).max).astype(jnp.int32)
                out = []
                for i in range(0, x.shape[0], block):
                    q, _ = _pre(w, x[i:i + block], p[i:i + block], items,
                                mode)
                    o = _attend(q, p[i:i + block], k, v, kpos, mode)
                    out.append(_post(w, x[i:i + block], o, items, is_moe,
                                     mode))
                new.append(jnp.concatenate(out))
            xs = new
            del w, kv
    res = []
    for i, s in enumerate(seqs):
        local = np.asarray(rows[i], np.int64) - P
        assert local.min() >= 0 and local.max() < len(s) - P
        xr = _pad_rows(xs[i + 1][jnp.asarray(local)], padded(len(local)))
        out = np.asarray(_head(xr, emb, arch["norm_eps"], mode))
        res.append(out[:len(local)])
    return res
