"""Config-driven LM: embedding -> grouped/scanned block stack -> head.

Layers are *grouped* so jax.lax.scan compiles each distinct block body once:
 - homogeneous stacks (dense/MoE/SSM) scan a single stacked group;
 - periodic hybrids (recurrentgemma's rglru,rglru,attn cycle) scan a stacked
   "superblock" group + unrolled remainder;
 - irregular prefixes (deepseek's 3 dense + 58 MoE layers) become run-length
   segments.
The KV/state cache pytree mirrors the grouping.  A dense cache rides the
layer scan as xs/ys next to the params; a paged pool is carried whole
instead (:func:`_paged_layers`), so no layer's pool is sliced out, relaid
out or restacked inside the scan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import attn_spec
from repro.core import mla as mla_mod
from repro.models import attention, frontend, layers, mamba, moe, rglru
from repro.sharding.rules import BATCH, constrain

AUX_KEYS = ("load_balance", "router_z")


# ------------------------------------------------------------- layer groups
def signatures(cfg) -> list:
    """(kind, is_moe) per layer."""
    return [(k, bool(cfg.moe_layer(i)) and k == "attn")
            for i, k in enumerate(cfg.layer_kinds())]


def _rle(seq):
    runs = []
    for s in seq:
        if runs and runs[-1][0] == s:
            runs[-1][1] += 1
        else:
            runs.append([s, 1])
    return [(s, n) for s, n in runs]


def layer_groups(cfg) -> list:
    """Static plan: list of {"sigs": [sig,...], "n": repeats}."""
    sigs = signatures(cfg)
    runs = _rle(sigs)
    if len(runs) <= 4:
        return [{"sigs": [s], "n": n} for s, n in runs]
    for p in range(1, 7):                              # periodic superblock
        if all(sigs[i] == sigs[i % p] for i in range(len(sigs))):
            n = len(sigs) // p
            groups = [{"sigs": sigs[:p], "n": n}]
            groups += [{"sigs": [s], "n": 1} for s in sigs[n * p:]]
            return groups
    return [{"sigs": [s], "n": 1} for s in sigs]       # fallback: unrolled


# -------------------------------------------------------------- block defs
def _init_block(key, cfg, sig, dtype):
    kind, is_moe = sig
    k1, k2, k3, k4 = jax.random.split(key, 4)
    D = cfg.d_model
    p = {"norm1": jnp.zeros((D,), dtype)}
    if kind == "attn":
        p["mix"] = (mla_mod.init_mla(k1, cfg, dtype) if cfg.attention_kind == "mla"
                    else attention.init_attention(k1, cfg, dtype))
    elif kind == "rglru":
        p["mix"] = rglru.init_rglru(k1, cfg, dtype)
    elif kind == "ssm":
        p["mix"] = mamba.init_mamba(k1, cfg, dtype)
        return p                                        # mamba block has no FFN
    else:
        raise ValueError(kind)
    p["norm2"] = jnp.zeros((D,), dtype)
    p["ffn"] = moe.init_moe(k2, cfg, dtype) if is_moe else \
        layers.init_mlp(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def _zero_aux():
    return {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}


def _block_seq(params, cfg, sig, x, positions, collect_cache: bool):
    """One block over a full sequence. Returns (x, aux, cache_rows_or_{})."""
    kind, is_moe = sig
    aux = _zero_aux()
    cache = {}
    h = layers.rms_norm(x, params["norm1"], cfg.norm_eps)
    if kind == "attn":
        fn = mla_mod.mla_train if cfg.attention_kind == "mla" else attention.attention_train
        if collect_cache:
            mixed, cache = fn(params["mix"], cfg, h, positions, return_cache=True)
        else:
            mixed = fn(params["mix"], cfg, h, positions)
    elif kind == "rglru":
        if collect_cache:
            mixed, cache = rglru.rglru_seq(params["mix"], cfg, h, return_state=True)
        else:
            mixed = rglru.rglru_seq(params["mix"], cfg, h)
    else:  # ssm
        if collect_cache:
            mixed, cache = mamba.mamba_seq(params["mix"], cfg, h, return_state=True)
        else:
            mixed = mamba.mamba_seq(params["mix"], cfg, h)
    x = x + mixed
    if kind == "ssm":
        return x, aux, cache
    h2 = layers.rms_norm(x, params["norm2"], cfg.norm_eps)
    if is_moe:
        f, aux = moe.moe_ffn(params["ffn"], cfg, h2)
    else:
        f = layers.mlp(params["ffn"], h2)
    return x + f, aux, cache


def _block_decode(params, cfg, sig, x, cache, pos, spec,
                  cache_layout="dense", block_table=None, lengths=None):
    """One block, one token. x: [B,D]. Returns (x, new_cache, sel).
    cache_layout "paged": the attention cache is a block pool; `pos` is
    replaced by per-sequence `lengths` + the shared `block_table`.  sel:
    the rows DeepSeek Sparse Attention selected (mla_decode_paged), else
    None."""
    kind, is_moe = sig
    h = layers.rms_norm(x, params["norm1"], cfg.norm_eps)
    sel = None
    if kind == "attn":
        if cache_layout == "paged" and cfg.attention_kind == "mla":
            mixed, cache, sel = mla_mod.mla_decode_paged(
                params["mix"], cfg, h, cache, block_table, lengths,
                spec=spec)
        elif cache_layout == "paged":
            mixed, cache = attention.attention_decode_paged(
                params["mix"], cfg, h, cache, block_table, lengths,
                spec=spec)
        elif cfg.attention_kind == "mla":
            mixed, cache = mla_mod.mla_decode(params["mix"], cfg, h, cache,
                                              pos, spec=spec)
        else:
            mixed, cache = attention.attention_decode(params["mix"], cfg, h,
                                                      cache, pos, spec=spec)
    elif kind == "rglru":
        mixed, cache = rglru.rglru_decode(params["mix"], cfg, h, cache)
    else:
        mixed, cache = mamba.mamba_decode(params["mix"], cfg, h, cache)
    x = x + mixed
    if kind == "ssm":
        return x, cache, sel
    h2 = layers.rms_norm(x, params["norm2"], cfg.norm_eps)
    if is_moe:
        # serving: one group of B tokens, dropless routing
        f, _ = moe.moe_ffn(params["ffn"], cfg, h2[None], dropless=True)
        f = f[0]
    else:
        f = layers.mlp(params["ffn"], h2)
    return x + f, cache, sel


def _init_block_cache(cfg, sig, batch: int, max_len: int, dtype):
    kind, _ = sig
    if kind == "attn":
        if cfg.attention_kind == "mla":
            return mla_mod.init_mla_cache(cfg, batch, max_len, dtype)
        return attention.init_attention_cache(cfg, batch, max_len, dtype)
    if kind == "rglru":
        return rglru.init_rglru_cache(cfg, batch, dtype)
    return mamba.init_mamba_cache(cfg, batch, dtype)


# ------------------------------------------------------------------- model
def init(rng, cfg):
    dtype = cfg.jax_dtype
    groups = layer_groups(cfg)
    keys = jax.random.split(rng, len(groups) + 2)
    params: dict = {
        "embed": layers.init_embed(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    fe = frontend.init_frontend(keys[1], cfg, dtype)
    if fe is not None:
        params["frontend"] = fe
    # each layer is drawn on its own and only then stacked, so no stacked
    # float32 draw of a whole group is ever held: the transient float32 is
    # one layer's largest matrix.  A layer's key is the one the vmapped
    # draw used, so the weights are bitwise the same.
    gp = []
    for g, key in zip(groups, keys[2:], strict=True):
        stack = []
        for k in jax.random.split(key, g["n"]):
            ks = jax.random.split(k, len(g["sigs"]))
            stack.append({f"b{j}": _init_block(ks[j], cfg, s, dtype)
                          for j, s in enumerate(g["sigs"])})
        gp.append(jax.tree.map(lambda *a: jnp.stack(a), *stack))
        del stack
    params["groups"] = gp
    return params


def _embed_inputs(params, cfg, batch):
    if "embeds" in batch:
        return frontend.apply_frontend(params["frontend"], batch["embeds"])
    return layers.embed(params["embed"], batch["tokens"])


def forward(params, cfg, batch, *, collect_cache: bool = False):
    """batch: {"tokens": [B,S]} or {"embeds": [B,S,Df], "targets": [B,S]}.
    Returns (logits [B,S,V], aux, cache or None)."""
    x = _embed_inputs(params, cfg, batch)
    # activations ride the batch axes; d_model replicated between blocks
    x = constrain(x, P(BATCH, None, None))
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    groups = layer_groups(cfg)
    aux_total = _zero_aux()
    caches = []

    for g, gparams in zip(groups, params["groups"], strict=True):
        def body(carry, xs, g=g):
            x, aux = carry
            lp = xs
            crows = {}
            for j, sig in enumerate(g["sigs"]):
                fn = _block_seq
                if cfg.remat:
                    fn = jax.checkpoint(fn, static_argnums=(1, 2, 5))
                x, a, c = fn(lp[f"b{j}"], cfg, sig, x, positions, collect_cache)
                # sequence parallelism: the residual stream (and hence the
                # per-layer remat residuals) is S-sharded over `model`.
                # Attention-free stacks (mamba) keep d_inner on `model`
                # instead — alternating layouts would round-trip the
                # activations through collectives every layer (§Perf M3).
                if cfg.attention_kind != "none":
                    x = constrain(x, P(BATCH, "model", None))
                else:
                    x = constrain(x, P(BATCH, None, None))
                crows[f"b{j}"] = c
                aux = {k: aux[k] + a[k] for k in AUX_KEYS}
            return (x, aux), crows

        (x, aux_total), gc = jax.lax.scan(body, (x, aux_total), gparams)
        caches.append(gc)

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = constrain(layers.unembed(params["embed"], x),
                       P(BATCH, None, "model"))   # vocab-sharded logits
    return logits, aux_total, (caches if collect_cache else None)


def loss_fn(params, cfg, batch):
    """Next-token cross-entropy (+ MoE aux). Returns (loss, metrics)."""
    logits, aux, _ = forward(params, cfg, batch)
    targets = batch.get("targets", batch.get("tokens"))
    lp = jax.nn.log_softmax(logits[:, :-1, :], axis=-1)
    tgt = targets[:, 1:]
    nll = -jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    total = loss + 0.01 * aux["load_balance"] + 0.001 * aux["router_z"]
    return total, {"nll": loss, **aux}


# ----------------------------------------------------------------- serving
def init_cache(cfg, batch: int, max_len: int):
    dtype = cfg.jax_dtype
    groups = layer_groups(cfg)

    def stack(leaf_fn, n):
        one = leaf_fn()
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), one)

    return [
        {f"b{j}": stack(lambda s=s: _init_block_cache(cfg, s, batch, max_len, dtype),
                        g["n"])
         for j, s in enumerate(g["sigs"])}
        for g in groups
    ]


def init_paged_cache(cfg, layout, kv_dtype: str = "fp"):
    """Paged serving cache: one KV block pool per layer (stacked per layer
    group, like :func:`init_cache`), all layers sharing ONE block table
    owned by the scheduler (runtime/paged_cache.BlockPool) — every layer
    sees the same sequence structure, so block ids are reused across
    layers and only the pools differ.  Attention-only stacks: recurrent /
    SSM state is per-sequence, not per-token — nothing to page.

    kv_dtype: "fp" (config dtype) | "int8" | "fp8" — quantized layouts
    store code pools plus per-row (scale, zp) pools under "*_sz" keys
    (DESIGN.md §11); every downstream path (decode, chunked prefill, COW
    block copy) keys off the cache dict, so the layout choice is made
    exactly once, here."""
    dtype = cfg.jax_dtype
    for kind in cfg.layer_kinds():
        if kind != "attn":
            raise ValueError(
                f"paged cache requires an attention-only stack (got {kind})")
    groups = layer_groups(cfg)

    def one(sig):
        if cfg.attention_kind == "mla":
            return mla_mod.init_mla_cache_paged(cfg, layout, dtype,
                                                kv_dtype=kv_dtype)
        return attention.init_attention_cache_paged(cfg, layout, dtype,
                                                    kv_dtype=kv_dtype)

    def stack(leaf_fn, n):
        one_c = leaf_fn()
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape),
                            one_c)

    return [
        {f"b{j}": stack(lambda s=s: one(s), g["n"])
         for j, s in enumerate(g["sigs"])}
        for g in groups
    ]


def paged_row_bytes(cfg, kv_dtype: str = "fp") -> int:
    """KV-cache bytes ONE token costs across the whole layer stack in a
    paged cache of the given layout — the quantity the serve loop's
    byte-budget capacity accounting divides by (DESIGN.md §11).  MLA: one
    latent row per layer at its stored, lane-aligned width, plus the
    indexer's key row under DeepSeek Sparse Attention; GQA: K heads ×
    head_dim for K and V each (each head is its own quantization granule,
    so each carries its own (scale, zp) overhead)."""
    from repro.runtime.paged_cache import lane_width, row_bytes
    n_layers = len(cfg.layer_kinds())
    if cfg.attention_kind == "mla":
        per_layer = row_bytes(lane_width(cfg.mla.latent_dim), kv_dtype,
                              cfg.jax_dtype)
        if cfg.mla.indexer is not None:
            per_layer += row_bytes(cfg.mla.indexer.head_dim, kv_dtype,
                                   cfg.jax_dtype)
        return n_layers * per_layer
    Kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    per_stream = row_bytes(Kv * hd, kv_dtype, cfg.jax_dtype, granules=Kv)
    return n_layers * 2 * per_stream                      # K and V pools


def copy_paged_block(cache, src: int, dst: int):
    """Copy-on-write device copy over the whole paged cache pytree:
    duplicate physical block `src` into `dst` in every layer's pool (leaves
    carry a leading stacked-layer axis, [n_layers, num_blocks, bs, *F]).
    The serve scheduler calls this on the COW pair returned by
    BlockPool.admit_shared when a cached prefix ends mid-block, BEFORE any
    chunk is appended to the new slot (DESIGN.md §10) — all layers share
    one block table, so one (src, dst) pair covers the whole stack.  The
    serve loop runs it jitted with the pool donated, so the copy is made
    in place (launch/serve.paged_programs)."""
    return jax.tree.map(lambda p: p.at[:, dst].set(p[:, src]), cache)


def read_paged_blocks(cache, ids):
    """HOST copies of physical blocks `ids` from every pool leaf of a paged
    cache pytree (leaves: [n_layers, num_blocks, bs, *F]) — the device→host
    leg of preemption-by-swap (DESIGN.md §12).  Returns a matching numpy
    pytree with [n_layers, len(ids), bs, *F] leaves, bitwise copies in the
    pool's storage dtype (codes AND sz scale pools both ride along, so a
    quantized sequence swaps losslessly).  np.asarray forces the host sync:
    the caller frees the device blocks right after, so the copy must be
    materialized, not a lazy view of in-flight state."""
    idx = np.asarray(ids, np.int32)
    return jax.tree.map(lambda p: np.asarray(p[:, idx]), cache)


def write_paged_blocks(cache, ids, rows):
    """Write host block rows (a pytree from :func:`read_paged_blocks`) back
    into physical blocks `ids` of every pool leaf — the host→device leg of
    swap restoration.  Dtypes already match (the host copy kept the pool's
    storage dtype), so the round-trip is bitwise and a restored sequence
    decodes exactly as if it had never been preempted.  Jitted like
    :func:`copy_paged_block` in the serve loop."""
    idx = jnp.asarray(ids, jnp.int32)
    return jax.tree.map(
        lambda p, r: p.at[:, idx].set(jnp.asarray(r).astype(p.dtype)),
        cache, rows)


def _block_prefill_chunk(params, cfg, sig, x, cache, table, lengths, spec,
                         qpos=None):
    """One block over a C-token prompt chunk against the paged cache.
    x: [B,C,D].  Paged caches are attention-only (init_paged_cache), so
    the recurrent/SSM kinds never reach here.  qpos [B,C] switches the
    attention layer to its draft-verification twin (explicit per-row
    causal horizon — DESIGN.md §14); everything else is identical.
    Returns (x, new_cache, sel): sel the last row's DeepSeek Sparse
    Attention mask (mla_prefill_chunk), else None."""
    kind, is_moe = sig
    assert kind == "attn", kind
    h = layers.rms_norm(x, params["norm1"], cfg.norm_eps)
    sel = None
    if qpos is None and cfg.attention_kind == "mla":
        mixed, cache, sel = mla_mod.mla_prefill_chunk(
            params["mix"], cfg, h, cache, table, lengths, spec=spec)
    elif qpos is None:
        mixed, cache = attention.attention_prefill_chunk(
            params["mix"], cfg, h, cache, table, lengths, spec=spec)
    else:
        fn = (mla_mod.mla_verify_chunk if cfg.attention_kind == "mla"
              else attention.attention_verify_chunk)
        mixed, cache = fn(params["mix"], cfg, h, cache, table, lengths,
                          qpos, spec=spec)
    x = x + mixed
    h2 = layers.rms_norm(x, params["norm2"], cfg.norm_eps)
    if is_moe:
        # serving semantics: DROPLESS routing, same as decode_step — a
        # prompt token is never dropped at inference. This deliberately
        # diverges from the capacity-dropped routing of the training-path
        # :func:`forward` that single-shot prefill reuses, so the chunked ==
        # single-shot equivalence oracle holds exactly only for non-MoE
        # stacks (or capacities that never drop, e.g. reduced configs);
        # tests/test_prefill_chunk.py checks MoE via chunked-vs-one-chunk
        # self-consistency instead.
        f, _ = moe.moe_ffn(params["ffn"], cfg, h2, dropless=True)
    else:
        f = layers.mlp(params["ffn"], h2)
    return x + f, cache, sel


def prefill_chunk(params, cfg, cache, tokens, block_table, lengths, *,
                  spec=None, with_selection: bool = False, **legacy):
    """CHUNKED paged prefill: run C prompt tokens per sequence directly
    against the block-pool serving cache (DESIGN.md §9).

    tokens: [B,C] the chunk (token c sits at absolute position
    lengths[b] + c); cache: the pytree from :func:`init_paged_cache`;
    block_table: [B,max_blocks]; lengths: [B] tokens already written — the
    caller (launch/serve.py) advances it by C between chunks
    (BlockPool.extend).  Each attention layer appends its chunk rows into
    its pool and attends causally over chunk + previously-written context,
    so there is NO dense staging cache and no post-hoc scatter — admission
    prefill becomes a sequence of per-chunk appends whose peak memory is
    one chunk, not one prompt.  Because `lengths` is the chunk's absolute
    start offset (positions, causal masking and the pool write all derive
    from it), a prefill may START at any nonzero offset: prefix-cache hits
    (DESIGN.md §10) map the matched blocks into the table, set lengths to
    the match length, and only the unmatched prompt TAIL ever runs through
    here.  Returns (logits [B,C,V], new cache); the final chunk's
    last-position logits seed the first decode token.  ``with_selection``
    (DeepSeek Sparse Attention) adds a third output: the chunk's last
    row's top-k mask in every layer, [n_layers, B, max_blocks*page]."""
    spec = attn_spec.coerce(spec, legacy, where="prefill_chunk")
    out = _chunk_forward(params, cfg, cache, tokens, block_table, lengths,
                         spec)
    return out if with_selection else out[:2]


def _paged_layers(params, cfg, cache, x, block_table, block):
    """Run every layer group over a paged cache that is carried whole.

    Each group's pool leaves ``[n, N, page, *F]`` enter the scan carry as
    one flat ``[n*N, page, *F]`` view; only the params and the layer index
    ride as ``xs``.  Layer ``i`` addresses its own N blocks through
    ``block_table + i*N``, so its null block is ``i*N`` and inactive slots
    stay masked as before.  The scatter writes the carry in place and the
    kernels read it through the table: no layer's pool is sliced out,
    relaid out or stacked back inside the scan.  The pool's rows are
    lane-aligned, so its default layout is the row-major one the kernels
    read and XLA relays it at no program boundary either (DESIGN.md §8).
    ``block(lp, sig, x, pool, table)`` runs one block and returns
    ``(x, pool, sel)``.  Returns (x, new cache, sels): sels the blocks'
    ``sel`` outputs stacked in layer order, None where no block gives one
    (DeepSeek Sparse Attention's selections, :func:`decode_step`)."""
    new_caches, sels = [], []
    for g, gparams, gcache in zip(layer_groups(cfg), params["groups"], cache,
                                  strict=True):
        n = g["n"]
        N = jax.tree.leaves(gcache)[0].shape[1]
        flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), gcache)

        def body(carry, xs, g=g, N=N):
            x, pools = carry
            lp, i = xs
            table = block_table + i * N
            new, sel = {}, []
            for j, sig in enumerate(g["sigs"]):
                x, new[f"b{j}"], s = block(lp[f"b{j}"], sig, x,
                                           pools[f"b{j}"], table)
                sel.append(s)
            return (x, new), (jnp.stack(sel) if all(
                s is not None for s in sel) else None)

        (x, flat), sel = jax.lax.scan(
            body, (x, flat), (gparams, jnp.arange(n, dtype=jnp.int32)))
        new_caches.append(jax.tree.map(lambda a, ref: a.reshape(ref.shape),
                                       flat, gcache))
        sels.append(None if sel is None
                    else sel.reshape((-1,) + sel.shape[2:]))
    if any(s is None for s in sels):
        return x, new_caches, None
    return x, new_caches, jnp.concatenate(sels)


def _chunk_forward(params, cfg, cache, tokens, block_table, lengths, spec,
                   qpos=None):
    """Shared chunk-shaped forward of prefill_chunk and verify_step."""
    x = constrain(layers.embed(params["embed"], tokens), P(BATCH, None, None))

    def block(lp, sig, x, pool, table):
        return _block_prefill_chunk(lp, cfg, sig, x, pool, table, lengths,
                                    spec, qpos)

    x, new_caches, sel = _paged_layers(params, cfg, cache, x, block_table,
                                       block)
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = layers.unembed(params["embed"], x)
    return logits, new_caches, sel


def verify_step(params, cfg, cache, tokens, block_table, lengths, *,
                spec=None, qpos=None, **legacy):
    """Score k draft tokens per sequence in ONE chunked-prefill-shaped pass
    (DESIGN.md §14) — the verification half of draft-then-verify decoding.

    tokens: [B,k] — row 0 is each sequence's last committed token, rows
    1..k-1 the draft continuation; the pass both APPENDS their KV rows into
    the paged pool at `lengths` (in-cache verification — the accepted
    prefix's rows are already where decode needs them) and returns logits
    for every draft position.  qpos: [B,k] per-row absolute positions; None
    → the linear chain lengths[:, None] + arange(k), under which this is
    bitwise identical to :func:`prefill_chunk` on the same tokens.  The
    caller pre-extends the block budget (BlockPool.extend) and rewinds the
    rejected tail afterwards (BlockPool.truncate(..., free_blocks=False)).
    Returns (logits [B,k,V], new cache)."""
    spec = attn_spec.coerce(spec, legacy, where="verify_step")
    if qpos is None:
        k = tokens.shape[1]
        qpos = lengths[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    return _chunk_forward(params, cfg, cache, tokens, block_table, lengths,
                          spec, qpos.astype(jnp.int32))[:2]


def _pad_cache_rows(cfg, sig, cache_rows, max_len, batch_s):
    """Pad per-layer prefill cache rows out to the serving cache layout."""
    kind, _ = sig
    if kind in ("rglru", "ssm"):
        return cache_rows
    if cfg.attention_kind == "mla":
        c = cache_rows["c"]
        pad = max_len - c.shape[1]
        return {"c": jnp.pad(c, ((0, 0), (0, pad), (0, 0)))}
    n = min(max_len, cfg.window_size) if cfg.attention_kind == "local" else max_len
    out = {}
    for key in ("k", "v"):
        rows = cache_rows[key]                          # [B,S,K,hd]
        S = rows.shape[1]
        if cfg.attention_kind == "local" and S > n:
            rows = rows[:, -n:]
        pad = n - rows.shape[1]
        out[key] = jnp.pad(rows, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return out


def prefill(params, cfg, batch, max_len: int):
    """Run the prompt, build the serving cache. Returns (last_logits, cache, pos)."""
    logits, _, caches = forward(params, cfg, batch, collect_cache=True)
    S = logits.shape[1]
    groups = layer_groups(cfg)
    padded = []
    for g, gc in zip(groups, caches, strict=True):
        padded.append({f"b{j}": jax.vmap(
            lambda rows, s=s: _pad_cache_rows(cfg, s, rows, max_len, S))(gc[f"b{j}"])
            for j, s in enumerate(g["sigs"])})
    return logits[:, -1, :], padded, S


def decode_step(params, cfg, cache, tokens, pos, *, spec=None,
                cache_layout: str = "dense",
                block_table=None, lengths=None,
                with_selection: bool = False, **legacy):
    """One serving step. tokens: [B] int32; pos: scalar index of the new token.
    Returns (logits [B,V], new_cache). spec: one AttnSpec carrying mode /
    kv_splits / rescale for every attention layer (legacy mode=/kv_splits=
    keywords shim through attn_spec.coerce).  spec.kv_splits None =
    auto-scheduled per layer geometry — serving picks up split-KV with zero
    caller changes; exception: the native-layout GQA XLA path only splits
    on an explicit count, since splitting there costs a cache reshuffle
    copy — see models/attention.gqa_decode.

    cache_layout "paged" (the serving default in launch/serve.py): `cache`
    is the pool pytree from :func:`init_paged_cache`, and `block_table`
    [B, max_blocks] + per-sequence `lengths` [B] replace the shared scalar
    `pos` — ragged sequences decode in one batch (continuous batching).
    ``with_selection`` (paged, DeepSeek Sparse Attention) adds a third
    output: the positions each layer selected, [n_layers, B, k], the
    first min(lengths + 1, k) of each row valid."""
    spec = attn_spec.coerce(spec, legacy, where="decode_step")
    assert cache_layout in ("dense", "paged"), cache_layout
    if cache_layout == "paged":
        assert block_table is not None and lengths is not None
    x = constrain(layers.embed(params["embed"], tokens), P(BATCH, None))
    if cache_layout == "paged":
        def block(lp, sig, x, pool, table):
            return _block_decode(lp, cfg, sig, x, pool, None, spec,
                                 cache_layout="paged", block_table=table,
                                 lengths=lengths)

        x, new_caches, sel = _paged_layers(params, cfg, cache, x,
                                           block_table, block)
    else:
        # a dense cache has no table to offset: it rides the scan as xs/ys
        new_caches = []
        for g, gparams, gcache in zip(layer_groups(cfg), params["groups"],
                                      cache, strict=True):
            def body(x, xs, g=g):
                lp, lc = xs
                ncs = {}
                for j, sig in enumerate(g["sigs"]):
                    x, ncs[f"b{j}"], _ = _block_decode(
                        lp[f"b{j}"], cfg, sig, x, lc[f"b{j}"], pos, spec)
                return x, ncs
            x, gc_new = jax.lax.scan(body, x, (gparams, gcache))
            new_caches.append(gc_new)
        sel = None
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = layers.unembed(params["embed"], x)
    return (logits, new_caches, sel) if with_selection else (logits,
                                                             new_caches)


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))
