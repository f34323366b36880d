"""Chunked paged ETAP prefill validation (DESIGN.md §9): kernel/XLA paths
vs a dense causally-masked oracle, model-level equivalence of ANY chunking
against single-shot prefill (block-aligned, unaligned, 1-chunk — the
acceptance grid — plus a hypothesis property over random partitions), and
the token-budget serve loop interleaving prefill chunks with decode steps.
All Pallas runs are interpret=True on CPU; tolerances match test_paged.py."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.configs import get_config, reduced
from repro.core.etap import etap_prefill_xla, prefill_attention_paged
from repro.kernels.etap import ops as etap_ops
from repro.models import model
from repro.runtime import paged_cache as pc

RNG = np.random.default_rng(23)


def _ref_prefill(q, k, v, start):
    """fp64 dense oracle: row softmax over key positions <= start + c."""
    q64 = np.asarray(q, np.float64)
    k64 = np.asarray(k, np.float64)
    v64 = np.asarray(v, np.float64)
    B, Cq, H, Dk = q64.shape
    S = k64.shape[1]
    scale = Dk ** -0.5
    out = np.zeros((B, Cq, H, v64.shape[-1]))
    kpos = np.arange(S)
    for b in range(B):
        s = np.einsum("chd,sd->chs", q64[b], k64[b]) * scale
        for c in range(Cq):
            live = kpos <= start[b] + c
            sc = s[c][:, live]
            p = np.exp(sc - sc.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b, c] = p @ v64[b][live]
    return out


def _rmse(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2)))


# starts deliberately straddle both page sizes: mid-page, page-aligned,
# one past a 64 boundary — the chunk always crosses at least one boundary.
S, CQ = 192, 11
STARTS = [5, 64, 65]


@pytest.mark.parametrize("page", [16, 64])
def test_prefill_kernel_paths_vs_ref(page):
    B, H, Dk, Dv = 3, 4, 32, 24
    q = jnp.asarray(RNG.normal(size=(B, CQ, H, Dk)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, Dk)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, Dv)), jnp.float32)
    start = jnp.asarray(STARTS, jnp.int32)
    ref = _ref_prefill(q, k, v, STARTS)
    scale = Dk ** -0.5
    # dense XLA twin
    assert _rmse(etap_prefill_xla(q, k, v, start, scale=scale, block=page),
                 ref) <= 1e-4
    # paged kernel + gather-XLA fallback on the same pool
    total = [s + CQ for s in STARTS]
    k_pool, bp = pc.dense_to_paged(k, total, pc.layout_for(B, S, page))
    v_pool, _ = pc.dense_to_paged(v, total, pc.layout_for(B, S, page))
    table, _ = bp.device_views()
    out_k = etap_ops.etap_prefill_paged(q, k_pool, v_pool, table, start,
                                        scale=scale)
    assert _rmse(out_k, ref) <= 1e-4
    out_x = prefill_attention_paged(q, k_pool, v_pool, table, start,
                                    scale=scale, use_kernels=False)
    assert _rmse(out_x, ref) <= 1e-4


def test_prefill_kernel_mla_fused_vs_ref():
    """Single latent pool, V = pool[..., :dv] — the paper's serving path."""
    B, H, D, dv, page = 2, 4, 48, 32, 16
    q = jnp.asarray(RNG.normal(size=(B, CQ, H, D)), jnp.float32)
    kv = jnp.asarray(RNG.normal(size=(B, S, D)), jnp.float32)
    start = jnp.asarray(STARTS[:B], jnp.int32)
    ref = _ref_prefill(q, kv, np.asarray(kv)[..., :dv], STARTS[:B])
    total = [s + CQ for s in STARTS[:B]]
    pool, bp = pc.dense_to_paged(kv, total, pc.layout_for(B, S, page))
    table, _ = bp.device_views()
    out = etap_ops.etap_prefill_mla_paged(q, pool, dv, table, start,
                                          scale=D ** -0.5)
    assert _rmse(out, ref) <= 1e-4


@pytest.mark.parametrize("verify", [False, True], ids=["prefill", "verify"])
def test_prefill_kernel_column_tiles_exact(monkeypatch, verify):
    """Tiling the Cq*H column axis over the grid — with a padded last tile
    — is the same math: every column carries its own softmax state."""
    from repro.kernels.etap import etap as kern
    B, H, D, dv, page = 2, 4, 48, 32, 16
    q = jnp.asarray(RNG.normal(size=(B, CQ, H, D)), jnp.float32)
    kv = jnp.asarray(RNG.normal(size=(B, S, D)), jnp.float32)
    start = jnp.asarray(STARTS[:B], jnp.int32)
    pool, bp = pc.dense_to_paged(kv, [s + CQ for s in STARTS[:B]],
                                 pc.layout_for(B, S, page))
    table, _ = bp.device_views()
    qpos = start[:, None] + jnp.arange(CQ, dtype=jnp.int32)[None, :]

    def run():
        if verify:
            return kern.etap_verify_mla_paged_pallas(
                q, pool, dv, table, start, qpos, scale=D ** -0.5)
        return kern.etap_prefill_mla_paged_pallas(
            q, pool, dv, table, start, scale=D ** -0.5)

    whole = run()
    monkeypatch.setattr(kern, "PREFILL_COL_TILE", 16)
    assert kern._col_tiling(CQ * H) == (16, 48)     # 44 columns: 3 tiles
    tiled = run()
    assert np.array_equal(np.asarray(tiled), np.asarray(whole))
    ref = _ref_prefill(q, kv, np.asarray(kv)[..., :dv], STARTS[:B])
    assert _rmse(tiled, ref) <= 1e-4


def test_prefill_kernel_shuffled_table():
    """The prefill kernel must follow the TABLE, not physical pool order."""
    page, n, H, Dk = 16, 6, 4, 32
    Sl = n * page
    q = jnp.asarray(RNG.normal(size=(1, CQ, H, Dk)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, Sl, Dk)), jnp.float32)
    start = jnp.asarray([Sl - CQ], jnp.int32)
    perm = RNG.permutation(np.arange(1, n + 1)).astype(np.int32)
    pool = np.zeros((n + 1, page, Dk), np.float32)
    pool[perm] = np.asarray(k[0]).reshape(n, page, Dk)
    out = etap_ops.etap_prefill_mla_paged(q, jnp.asarray(pool), Dk,
                                          perm[None, :], start,
                                          scale=Dk ** -0.5)
    ref = _ref_prefill(q, k, np.asarray(k), [Sl - CQ])
    assert _rmse(out, ref) <= 1e-4


# ------------------------------------------------- model-level equivalence
@pytest.fixture(scope="module")
def mla_model():
    """Reduced deepseek (the paper's arch) without MoE: the top-k router is
    discontinuous, so float noise between the naive single-shot and
    absorbed chunked attention orders could flip an expert at a near-tie
    gate — an O(1e-2) logit jump unrelated to the chunking under test."""
    cfg = dataclasses.replace(reduced(get_config("deepseek_r1_671b")),
                              moe=None)
    return cfg, model.init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def gqa_model():
    cfg = reduced(get_config("qwen3_8b"), kv_heads=2)
    return cfg, model.init(jax.random.PRNGKey(0), cfg)


def _check_chunking(cfg, params, toks, chunks, *, page=8, atol=2e-4):
    """Chunked paged prefill over `chunks` must match the single-shot dense
    forward at EVERY prompt position (a strictly stronger check than the
    final logits single-shot model.prefill returns)."""
    B, P = toks.shape
    assert sum(chunks) == P
    full, _, _ = model.forward(params, cfg, {"tokens": toks})
    layout = pc.layout_for(B, P, block_size=page)
    bp = pc.BlockPool(layout, B)
    paged = model.init_paged_cache(cfg, layout)
    for b in range(B):
        assert bp.admit(0, P) == b           # cold admission, blocks only
    lgs, lo = [], 0
    for c in chunks:
        table, lengths = bp.device_views()
        lg, paged = model.prefill_chunk(params, cfg, paged,
                                        toks[:, lo:lo + c], table, lengths)
        lgs.append(lg)
        lo += c
        for b in range(B):
            bp.extend(b, c)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(lgs, axis=1)),
                               np.asarray(full), atol=atol, rtol=1e-3)


# the acceptance grid: block-aligned, unaligned (straddles 8-token pages),
# and the whole prompt in one chunk
CHUNKINGS = {"aligned": (8, 8, 8), "unaligned": (5, 11, 8), "one": (24,)}


@pytest.mark.parametrize("chunks", CHUNKINGS.values(), ids=CHUNKINGS.keys())
def test_chunked_prefill_matches_single_shot_mla(mla_model, chunks):
    cfg, params = mla_model
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                              cfg.vocab_size)
    _check_chunking(cfg, params, toks, chunks)


def test_chunked_prefill_matches_single_shot_mla_kernels(mla_model):
    """Same contract through the Pallas prefill kernel (interpret mode)."""
    cfg, params = mla_model
    cfg = dataclasses.replace(cfg, use_kernels=True)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                              cfg.vocab_size)
    _check_chunking(cfg, params, toks, (5, 11, 8))


def test_chunked_prefill_matches_single_shot_gqa(gqa_model):
    """The generic grouped-query attention stack pages + chunks too."""
    cfg, params = gqa_model
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0,
                              cfg.vocab_size)
    _check_chunking(cfg, params, toks, (7, 9, 8))


def _random_partition(rng, total):
    chunks = []
    while total:
        c = int(rng.integers(1, total + 1))
        chunks.append(c)
        total -= c
    return tuple(chunks)


if HAVE_HYPOTHESIS:
    @settings(max_examples=6, deadline=None)
    @given(data=st.data())
    def test_chunked_prefill_any_partition(mla_model, data):
        """Property: ANY partition of the prompt — chunk sizes free to be
        indivisible by (and straddle) the pool block size — matches the
        single-shot forward."""
        cfg, params = mla_model
        P = data.draw(st.integers(min_value=4, max_value=32), label="P")
        chunks, left = [], P
        while left:
            c = data.draw(st.integers(min_value=1, max_value=left),
                          label="chunk")
            chunks.append(c)
            left -= c
        toks = jax.random.randint(jax.random.PRNGKey(P), (1, P), 0,
                                  cfg.vocab_size)
        _check_chunking(cfg, params, toks, tuple(chunks))
else:
    def test_chunked_prefill_any_partition(mla_model):
        """Deterministic stand-in for the hypothesis property (keeps the
        tier-1 skip count flat when hypothesis is absent): seeded random
        partitions of random prompt lengths."""
        cfg, params = mla_model
        rng = np.random.default_rng(7)
        for _ in range(4):
            P = int(rng.integers(4, 33))
            toks = jax.random.randint(jax.random.PRNGKey(P), (1, P), 0,
                                      cfg.vocab_size)
            _check_chunking(cfg, params, toks, _random_partition(rng, P))


def test_chunked_prefill_moe_self_consistent():
    """MoE stacks chunk too — through the serving (dropless) router, which
    deliberately diverges from single-shot prefill's capacity-dropped
    training router (see model._block_prefill_chunk).  The oracle here is
    therefore SELF-consistency: many chunks vs one chunk, both through
    prefill_chunk, must agree.  Tolerance is loose because the top-k gate
    is discontinuous — float noise between the two chunkings' attention
    summation orders may flip an expert at a near-tie (an O(1e-2) jump);
    wiring bugs are O(1)."""
    cfg = reduced(get_config("deepseek_r1_671b"))
    assert cfg.moe is not None
    params = model.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 24), 0,
                              cfg.vocab_size)

    def run(chunks):
        layout = pc.layout_for(2, 24, block_size=8)
        bp = pc.BlockPool(layout, 2)
        paged = model.init_paged_cache(cfg, layout)
        for b in range(2):
            assert bp.admit(0, 24) == b
        lgs, lo = [], 0
        for c in chunks:
            table, lengths = bp.device_views()
            lg, paged = model.prefill_chunk(params, cfg, paged,
                                            toks[:, lo:lo + c], table,
                                            lengths)
            lgs.append(lg)
            lo += c
            for b in range(2):
                bp.extend(b, c)
        return np.asarray(jnp.concatenate(lgs, axis=1))

    np.testing.assert_allclose(run((5, 11, 8)), run((24,)), atol=5e-2,
                               rtol=0)


# ------------------------------------------------------------- serve loop
def test_serve_interleaves_prefill_chunks_with_decode():
    """Under a small per-step token budget the scheduler must (a) split
    admission prefill into chunks and (b) keep decoding in the same steps —
    no admission stall — while every request still gets exactly its
    budgeted tokens."""
    from repro.launch import serve

    args = serve.parse_args([
        "--reduced", "--batch", "2", "--prompt", "32", "--gen", "8",
        "--requests", "4", "--page-size", "16", "--cache-layout", "paged",
        "--prefill-chunk", "8", "--token-budget", "10"])
    res = serve.run(args)
    assert len(res["outputs"]) == 4          # every request served
    gens = {i: len(v) for i, v in res["outputs"].items()}
    assert res["tokens_served"] == sum(gens.values())
    assert all(n in (4, 8) for n in gens.values())  # the two gen buckets
    # prompts (16/24/32 tokens) must have run as multiple 8-token chunks...
    assert res["prefill_chunks"] >= 2 * len(res["outputs"])
    # ...and decode steps must have been taken in the same scheduler steps
    # as prefill chunks — the no-head-of-line-blocking acceptance check.
    assert res["interleaved_steps"] > 0
    assert res["steps"] >= max(gens.values())
