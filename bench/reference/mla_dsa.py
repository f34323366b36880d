"""Plain float32 reference of the DeepSeek-V3.2 stack: the DeepSeek-V3
stack of ``mla_moe.py`` (reused by import, unchanged) with DeepSeek Sparse
Attention in every layer.  No cache, no kernels, no batching.

The lightning indexer, per token t (h the layer's normed input, cq the
MLA's normed q latent):

    q_idx[t] = cq[t] W_q                  [heads, dim], rope on the first
    k_idx[t] = LayerNorm(h[t] W_k)        [dim]          qk_rope columns
    w[t]     = h[t] W_w · heads^-1/2 · dim^-1/2          [heads]
    I(t, s)  = sum_h w[t, h] · ReLU(q_idx[t, h] · k_idx[s]),  s <= t

and query t's attention keeps only its min(index_topk, t + 1) causal rows
with the largest scores (ties to the earlier row): every other row is
masked out of the softmax.  The scores are computed over all causal rows
in every layer, a block of query rows at a time and one index head at a
time, so they fit at 33K tokens.

In ``mode="fp32"`` the index queries and keys are rounded to the
configuration's dtype (bfloat16) before scoring, the dtype the program
keeps them in, so that near-ties select alike; the products and sums stay
float32 at ``precision="highest"``.
In ``mode="fp8"`` (the control) every product's inputs are rounded to
float8_e4m3fn, the indexer's included.  The departures of the
configuration file hold here as in ``mla_moe.py``; the indexer runs
without the published model's float8 Hadamard-rotated form.

The weights are drawn from the seed as the program's initialiser draws
them: the indexer's from a key folded off each layer's MLA key.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from reference import mla_moe as base

F32 = base.F32


def indexer_weights(key, arch: dict) -> dict:
    """One layer's indexer weights; ``key`` is the layer's block key."""
    dt = jnp.dtype(arch["dtype"])
    D, qr = arch["d_model"], arch["q_lora_rank"]
    nh, hd = arch["index_heads"], arch["index_dim"]
    k_mla = jax.random.split(key, 4)[0]
    ks = jax.random.split(jax.random.fold_in(k_mla, 7), 3)
    return {"w_q": base._draw(ks[0], (qr, nh * hd), qr ** -0.5, dt),
            "w_k": base._draw(ks[1], (D, hd), D ** -0.5, dt),
            "w_w": base._draw(ks[2], (D, nh), D ** -0.5, dt)}


def _layer_norm(x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope_head(x, pos, rope, theta):
    return jnp.concatenate([base._rope(x[..., :rope], pos, theta),
                            x[..., rope:]], -1)


@partial(jax.jit, static_argnums=(4, 5))
def _index(w, iw, x, pos, arch_items, mode):
    """Rows x [R, D] at pos -> index queries [R, heads, dim], keys
    [R, dim] and head weights [R, heads]."""
    a = dict(arch_items)
    eps, rope, theta = a["norm_eps"], a["qk_rope"], a["rope_theta"]
    h = base._rms(x, eps)
    cq = base._rms(base._ein("sd,dq->sq", h, w["w_dq"], mode), eps)
    q = base._ein("sq,qf->sf", cq, iw["w_q"], mode).reshape(
        x.shape[0], a["index_heads"], a["index_dim"])
    q = _rope_head(q, pos, rope, theta)
    k = _layer_norm(base._ein("sd,dk->sk", h, iw["w_k"], mode), eps)
    k = _rope_head(k, pos, rope, theta)
    if mode == "fp32":                 # the program's q and cached k
        q = q.astype(a["dtype"]).astype(F32)
        k = k.astype(a["dtype"]).astype(F32)
    wt = base._ein("sd,dh->sh", h, iw["w_w"], mode) * (
        a["index_heads"] ** -0.5 * a["index_dim"] ** -0.5)
    return q, k, wt


@partial(jax.jit, static_argnums=(5, 6, 7))
def _select(q, wt, qpos, k, kpos, topk, mode, heads):
    """Top-k masks [R, K] of query rows over keys at kpos <= qpos: the
    min(topk, causal rows) causal rows with the largest index scores,
    ties to the earlier row (``jax.lax.top_k``'s order; a zero score is
    +0.0).  Returns (masks, scores [R, K], -inf off the causal rows)."""
    def head(acc, h):
        s = base._ein("sd,kd->sk", q[:, h], k, mode)
        return acc + wt[:, h, None] * jnp.maximum(s, 0.0), None

    scores, _ = jax.lax.scan(head, jnp.zeros((q.shape[0], k.shape[0]), F32),
                             jnp.arange(heads))
    causal = kpos[None, :] <= qpos[:, None]
    scores = jnp.where(causal, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    _, idx = jax.lax.top_k(scores, min(topk, k.shape[0]))
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(q.shape[0])[:, None], idx].set(True)
    return causal & picked, scores


@jax.jit
def _gate(sel, scores, prog_pos, want, first_layer, band, overlap):
    """The program's recorded selections against one block of query rows:
    ``sel`` [R, K] this reference's own top-k masks, ``scores`` [R, K]
    its index scores (-inf off the causal rows), ``prog_pos`` [R, n] the
    recorded positions (K where none), ``want`` [R] the count a row must
    select (0 where nothing is recorded).  Per row: the best unselected
    causal row's score less the worst selected row's, over the standard
    deviation of the row's causal scores (<= 0 for an exact top-k); the
    share of the recorded rows in ``sel``; and whether the record passes
    the gate of :func:`logits`.  Returns (masks with each passing record
    in place of ``sel``'s row, passed, margin, share)."""
    R, K = sel.shape
    prog = jnp.zeros((R, K), bool).at[jnp.arange(R)[:, None], prog_pos].set(
        True, mode="drop")
    causal = scores > -jnp.inf
    lo = jnp.min(jnp.where(prog, scores, jnp.inf), axis=-1)
    hi = jnp.max(jnp.where(causal & ~prog, scores, -jnp.inf), axis=-1)
    x = jnp.where(causal, scores, 0.0)
    n = jnp.maximum(jnp.sum(causal, axis=-1), 1)
    mean = jnp.sum(x, axis=-1) / n
    var = jnp.sum(jnp.where(causal, (x - mean[:, None]) ** 2, 0.0),
                  axis=-1) / n
    margin = (hi - lo) / jnp.sqrt(jnp.maximum(var, 1e-30))
    size = jnp.sum(prog, axis=-1)
    share = jnp.sum(prog & sel, axis=-1) / jnp.maximum(size, 1)
    ok = ((want > 0) & (size == want) & jnp.all(causal | ~prog, axis=-1)
          & (share >= overlap) & (~first_layer | (margin <= band)))
    return jnp.where(ok[:, None], prog, sel), ok, margin, share


@partial(jax.jit, static_argnums=(4,))
def _attend(q, k, v, sel, mode):
    """Softmax attention of query rows over the keys ``sel`` keeps."""
    scale = q.shape[-1] ** -0.5
    s = base._ein("qhd,khd->hqk", q, k, mode) * scale
    s = jnp.where(sel[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = base._ein("hqk,khd->qhd", p, v, mode)
    return o.reshape(q.shape[0], -1)


def _adopt(sel, scores, record, layer, first, count, seq, gate, check):
    """The masks of one block of query rows (positions first ..
    first + count - 1) of sequence ``seq``: the program's recorded
    selection where ``record`` ({position: [layers, n] positions}) holds
    one that passes ``gate`` (see :func:`logits`), else this reference's
    own.  Adds to ``check``."""
    at = [pos for pos in range(first, first + count) if pos in record]
    if not at:
        return sel
    topk = int(check["topk"])
    # fixed shapes, so the gate compiles once per call
    prog_pos = np.full((sel.shape[0], topk), sel.shape[1], np.int32)
    want = np.zeros(sel.shape[0], np.int32)
    for pos in at:
        got = np.asarray(record[pos][layer], np.int64)
        r = pos - first
        prog_pos[r, :min(len(got), topk)] = got[:topk]
        want[r] = min(topk, pos + 1) if len(got) <= topk else -1
    sel, ok, margin, share = _gate(sel, scores, prog_pos, want, layer == 0,
                                   gate["band"], gate["overlap"])
    rows = np.asarray(at) - first
    ok, margin, share = (np.asarray(a)[rows] for a in (ok, margin, share))
    mine = check["seqs"].setdefault(seq, {"rows": 0, "first_margin": -np.inf,
                                          "margin": -np.inf, "share": 1.0,
                                          "missed": []})
    mine["rows"] += len(at)
    key = "first_margin" if layer == 0 else "margin"
    mine[key] = max(mine[key], float(margin.max()))
    mine["share"] = min(mine["share"], float(share.min()))
    mine["missed"] += [(int(pos), layer) for pos, k in zip(at, ok,
                                                           strict=True)
                       if not k]
    return sel


def logits(arch: dict, seed: int, seqs: list, rows: list, *,
           mode: str = "fp32", block: int = 512, key_align: int = 4096,
           selected: list | None = None, band: float = 0.0,
           overlap: float = 0.0, check: dict | None = None):
    """Reference logits of ``seqs`` at ``rows``, as ``mla_moe.logits``
    computes them (the sequences' common prefix once, layers one at a time,
    rows in blocks of ``block``), with each query row attending only its
    top-k rows by index score.  Returns a list of float32 numpy arrays
    [len(rows[i]), vocab].

    ``selected`` (one entry per sequence, or None): the program's record
    of the rows it selected, {position: [layers, n] positions}.  Selection
    is a step function of the index scores: rounding alone moves rows
    across the k-th score (near-ties), and a comparison under one
    selection each sees those flips, not the program's faults.  So where
    a sequence's record holds a row, this reference attends the program's
    rows in place of its own, in every layer, if every recorded row-layer
    of the sequence passes:

    * count min(index_topk, causal rows), all causal;
    * at least ``overlap`` of them in this reference's own top-k (a wrong
      key pool or a shifted row selects next to nothing of it);
    * in the first layer, where the program's indexer reads the same
      inputs as this one up to rounding, no unselected causal row scoring
      more than ``band`` standard deviations of the row's causal scores
      above the worst selected one.  Later layers carry every earlier
      layer's near-tie flips of the unrecorded rows, which move their
      scores by far more than rounding; the layer scan runs one attention
      body for every layer of a group, so the first layer sees a fault of
      the indexer or the selection as the later ones would.

    A sequence with any row-layer that fails is judged under this
    reference's own top-k everywhere.  ``check`` (a dict) receives, per
    sequence index under ``seqs``, the row-layers compared (``rows``), the
    largest first-layer and later-layer margins, the smallest share, the
    (position, layer) of each that failed (``missed``), and ``adopted``."""
    if check is None:
        check = {}
    check.update(topk=int(arch["index_topk"]), seqs={})
    gate = {"band": band, "overlap": overlap}
    out = _logits(arch, seed, seqs, rows, mode, block, key_align, selected,
                  gate, check)
    failed = {i for i, c in check["seqs"].items() if c["missed"]}
    for i, c in check["seqs"].items():
        c["adopted"] = i not in failed
    if failed:
        out = _logits(arch, seed, seqs, rows, mode, block, key_align,
                      [None if i in failed else r
                       for i, r in enumerate(selected)], gate,
                      {"topk": check["topk"], "seqs": {}})
    return out


def _logits(arch, seed, seqs, rows, mode, block, key_align, selected, gate,
            check):
    seqs = [np.asarray(s, np.int32) for s in seqs]
    P = base._common_prefix(seqs)
    segs = [seqs[0][:P]] + [s[P:] for s in seqs]
    starts = [0] + [P] * len(seqs)
    kpad = -(-max(len(s) for s in seqs) // key_align) * key_align
    items = base._items(arch)
    topk, heads = int(arch["index_topk"]), int(arch["index_heads"])
    emb_key, block_keys = base.layer_keys(seed, arch)
    emb = base.embed_weights(emb_key, arch)

    def padded(n):
        return -(-n // block) * block

    xs = [base._pad_rows(jnp.take(emb, jnp.asarray(t), axis=0),
                         padded(len(t))) for t in segs]
    pos = [jnp.asarray(base._pad_rows(
        np.arange(s0, s0 + len(t), dtype=np.int32), padded(len(t))))
        for t, s0 in zip(segs, starts, strict=True)]
    with jax.default_matmul_precision("highest"):
        for layer, key in enumerate(block_keys):
            is_moe = bool(arch["moe_layers"][layer])
            w = base.layer_weights(key, arch, is_moe)
            iw = indexer_weights(key, arch)
            kv, kidx = [], []
            for x, p, t in zip(xs, pos, segs, strict=True):
                if len(t) == 0:
                    kv.append(None)
                    kidx.append(None)
                    continue
                c = jnp.concatenate([
                    base._pre(w, x[i:i + block], p[i:i + block], items,
                              mode)[1] for i in range(0, x.shape[0], block)])
                ki = jnp.concatenate([
                    _index(w, iw, x[i:i + block], p[i:i + block], items,
                           mode)[1] for i in range(0, x.shape[0], block)])
                k, v = base._kv(w, c, items, mode)
                kv.append((k[:len(t)], v[:len(t)]))
                kidx.append(ki[:len(t)])
            new = []
            for j, (x, p, t) in enumerate(zip(xs, pos, segs, strict=True)):
                if len(t) == 0:
                    new.append(x)
                    continue
                own = [0, j] if j > 0 and P > 0 else [j]
                k = base._pad_rows(jnp.concatenate([kv[i][0] for i in own]),
                                   kpad)
                v = base._pad_rows(jnp.concatenate([kv[i][1] for i in own]),
                                   kpad)
                ki = base._pad_rows(jnp.concatenate([kidx[i] for i in own]),
                                    kpad)
                nk = starts[j] + len(t)
                kpos = jnp.where(jnp.arange(kpad) < nk, jnp.arange(kpad),
                                 np.iinfo(np.int32).max).astype(jnp.int32)
                record = (selected[j - 1] if j > 0 and selected is not None
                          else None)
                out = []
                for i in range(0, x.shape[0], block):
                    xb, pb = x[i:i + block], p[i:i + block]
                    q, _ = base._pre(w, xb, pb, items, mode)
                    qi, _, wt = _index(w, iw, xb, pb, items, mode)
                    sel, scores = _select(qi, wt, pb, ki, kpos, topk, mode,
                                          heads)
                    if record:
                        sel = _adopt(sel, scores, record, layer,
                                     starts[j] + i, min(block, len(t) - i),
                                     j - 1, gate, check)
                    o = _attend(q, k, v, sel, mode)
                    out.append(base._post(w, xb, o, items, is_moe, mode))
                new.append(jnp.concatenate(out))
            xs = new
            del w, iw, kv, kidx
    res = []
    for i, s in enumerate(seqs):
        local = np.asarray(rows[i], np.int64) - P
        assert local.min() >= 0 and local.max() < len(s) - P
        xr = base._pad_rows(xs[i + 1][jnp.asarray(local)],
                            padded(len(local)))
        out = np.asarray(base._head(xr, emb, arch["norm_eps"], mode))
        res.append(out[:len(local)])
    return res
