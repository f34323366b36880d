"""DeepSeek Sparse Attention (DeepSeek-V3.2) on the paged serve path, on
the CPU at a small size whose top-k (24 rows) is well below the contexts
(up to 64 rows), so that the selection decides what each query attends.

* the index-score kernel (interpret mode) against its XLA twin and a
  numpy loop;
* the top-k: a context shorter than top-k keeps every causal row; the
  mask is exactly ``jax.lax.top_k``'s pick, ties to the earlier row;
* the sparse decode equal to a dense decode under the top-k mask;
* chunked prefill and paged decode through ``run_paged`` against the
  plain float32 reference (bench/reference/mla_dsa.py), and the same
  comparison failing with the selection dropped;
* prefix cache on and off bitwise equal, COW and swap carrying the index
  keys;
* an R1-shaped configuration building the same parameters and cache as
  without the indexer, drawn bitwise as the stacked vmapped draw drew
  them; the byte count of a row; the refusals of run_paged.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import IndexerConfig, get_config, reduced
from repro.core import attn_spec, etap
from repro.launch import serve
from repro.models import model
from repro.runtime import paged_cache as pc

BENCH = Path(__file__).resolve().parents[1] / "bench"
TOPK = 24


def tiny_json(topk: int = TOPK) -> dict:
    """config.json keys of a 3 dense + 1 MoE DeepSeek-V3.2 stack at CPU
    size, in float32 (the program and the reference then agree to float
    rounding, so a moved selection shows)."""
    return {"model_type": "deepseek_v32", "hidden_size": 64,
            "intermediate_size": 128, "moe_intermediate_size": 32,
            "num_hidden_layers": 4, "num_attention_heads": 4,
            "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
            "v_head_dim": 16, "n_routed_experts": 4,
            "num_experts_per_tok": 2, "n_shared_experts": 1,
            "first_k_dense_replace": 3, "moe_layer_freq": 1,
            "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
            "dtype": "float32", "index_n_heads": 4, "index_head_dim": 16,
            "index_topk": topk}


@pytest.fixture(scope="module")
def v32():
    """The benchmark's deepseek_v32 architecture module."""
    sys.path.insert(0, str(BENCH))
    from harness import config
    return config.architecture({"model_type": "deepseek_v32"},
                               BENCH / "arch")


def dsa_cfg(topk: int = TOPK, dtype: str = "float32"):
    cfg = reduced(get_config("deepseek_r1_671b"), layers=3)
    return dataclasses.replace(cfg, dtype=dtype, mla=dataclasses.replace(
        cfg.mla, indexer=IndexerConfig(n_heads=4, head_dim=16, topk=topk)))


# --------------------------------------------------------- index scores
def _paged_keys(seed, B=3, nb=6, page=8, hd=16, heads=4, R=1):
    rng = np.random.default_rng(seed)
    N = 1 + B * nb
    pool = jnp.asarray(rng.standard_normal((N, page, hd)), jnp.float32)
    table = jnp.asarray(1 + np.arange(B * nb).reshape(B, nb)[:, ::-1].copy(),
                        jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, R, heads, hd)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, R, heads)), jnp.float32)
    limit = jnp.asarray([nb * page, 19, 1], jnp.int32)[:B]
    return q, w, pool, table, limit


@pytest.mark.parametrize("R", [1, 5, 12])
def test_index_kernel_matches_twin_and_a_loop(R):
    from repro.kernels.etap.dsa import dsa_index_pallas
    q, w, pool, table, limit = _paged_keys(0, R=R)
    got = dsa_index_pallas(q, w, pool, table, limit, interpret=True,
                           pages=4, row_tile=8)
    twin = etap.index_scores_xla(q, w, pool, table, limit)
    np.testing.assert_allclose(np.asarray(got), np.asarray(twin),
                               rtol=1e-5, atol=1e-5)
    qn, wn, pn, tn = (np.asarray(a, np.float64) for a in (q, w, pool, table))
    page = pool.shape[1]
    for b in range(q.shape[0]):
        for r in range(R):
            for s in range(table.shape[1] * page):
                want = -np.inf
                if s < int(limit[b]):
                    k = pn[int(tn[b, s // page]), s % page]
                    want = sum(wn[b, r, h] * max(qn[b, r, h] @ k, 0.0)
                               for h in range(q.shape[2]))
                np.testing.assert_allclose(float(got[b, r, s]), want,
                                           rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- top-k
def test_short_context_keeps_every_causal_row():
    """A context shorter than top-k: every causal row is selected, in the
    decode step's gather and in the prefill chunk's mask."""
    q, w, pool, table, limit = _paged_keys(1, R=3)
    lat = jnp.asarray(np.random.default_rng(2).standard_normal(
        (pool.shape[0], pool.shape[1], 12)), jnp.float32)
    scores = etap.index_scores_xla(q[:, :1], w[:, :1], pool, table,
                                   limit)[:, 0]
    rows, valid, idx = etap.dsa_select(scores, lat, table, limit,
                                       topk=1000)
    assert np.asarray(valid).tolist() == np.asarray(limit).tolist()
    for b in range(3):
        n = int(limit[b])
        got = {tuple(np.asarray(r)) for r in rows[b, :n]}
        flat = np.asarray(pc.gather_blocks(lat, table))[b, :n]
        assert got == {tuple(r) for r in flat}
        assert sorted(np.asarray(idx[b, :n]).tolist()) == list(range(n))
    spec = attn_spec.AttnSpec(use_kernels=False)
    start = jnp.asarray([5, 0, 9], jnp.int32)
    sel = etap.dsa_prefill_select(q, w, pool, table, start, spec=spec,
                                  topk=1000)
    pos = np.arange(sel.shape[-1])
    for b in range(3):
        for c in range(3):
            assert (np.asarray(sel[b, c]) == (pos <= int(start[b]) + c)).all()


def test_top_k_mask_is_top_ks_pick():
    """Many ties, -inf (past a row's causal end) and +-0.0: the mask is
    the entries top_k lists, ties to the earlier index."""
    rng = np.random.default_rng(3)
    for i in range(60):
        x = rng.integers(0, 5, size=(4, 23)).astype(np.float32)
        if i % 2:
            x = rng.standard_normal((4, 23)).astype(np.float32)
            x[:, rng.random(23) < 0.4] = -np.inf
            x[0, :3], x[1, :2] = 0.0, -0.0
        k = int(rng.integers(1, 24))
        got = np.asarray(etap.top_k_mask(jnp.asarray(x), k))
        _, idx = jax.lax.top_k(jnp.asarray(x), k)
        want = np.zeros_like(got)
        np.put_along_axis(want, np.asarray(idx), True, axis=1)
        assert (got == want).all()


def test_sparse_decode_equals_dense_decode_under_the_mask():
    """The gathered top-k decode is the dense paged decode with every
    unselected row masked out of the softmax."""
    B, H, nb, page, lat_d, dv = 3, 4, 6, 8, 40, 32
    rng = np.random.default_rng(4)
    q_idx, w, kpool, table, lengths = _paged_keys(5, B=B, nb=nb, page=page)
    pool = jnp.asarray(rng.standard_normal((kpool.shape[0], page, lat_d)),
                       jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, lat_d)), jnp.float32)
    spec = attn_spec.AttnSpec(scale=lat_d ** -0.5, use_kernels=False)
    got, idx = etap.dsa_decode_paged(q, pool, kpool, q_idx[:, 0], w[:, 0],
                                     table, lengths, spec=spec, dv=dv,
                                     topk=10)
    scores = etap.index_scores_xla(q_idx, w, kpool, table, lengths)[:, 0]
    keep = np.asarray(etap.top_k_mask(scores, 10)) & (
        np.arange(nb * page)[None] < np.asarray(lengths)[:, None])
    k = np.asarray(pc.gather_blocks(pool, table), np.float64)
    for b in range(B):
        n = min(10, int(lengths[b]))              # the positions it reports
        assert sorted(np.asarray(idx[b, :n]).tolist()) == \
            np.flatnonzero(keep[b]).tolist()
        s = np.einsum("hd,ld->hl", np.asarray(q[b], np.float64), k[b]) \
            * lat_d ** -0.5
        s = np.where(keep[b][None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(got[b]), p @ k[b][:, :dv],
                                   rtol=2e-5, atol=2e-5)
    kern, _ = etap.dsa_decode_paged(q, pool, kpool, q_idx[:, 0], w[:, 0],
                                    table, lengths,
                                 spec=spec.replace(use_kernels=True,
                                                   interpret=True),
                                 dv=dv, topk=10)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(got),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("col_tile", [2048, 16])
def test_masked_prefill_kernel_matches_twin(monkeypatch, col_tile):
    """The paged ETAP prefill kernel under a top-k mask (interpret mode),
    in one column tile and in several with a padded last one, equals its
    XLA twin and a float64 masked softmax."""
    from repro.kernels.etap import etap as kern
    from repro.kernels.etap import ops as etap_ops
    monkeypatch.setattr(kern, "PREFILL_COL_TILE", col_tile)
    B, C, H, D, dv, page, nb = 2, 7, 4, 24, 16, 8, 6
    rng = np.random.default_rng(9)
    pool = jnp.asarray(rng.standard_normal((1 + B * nb, page, D)),
                       jnp.float32)
    table = jnp.asarray(1 + np.arange(B * nb).reshape(B, nb)[:, ::-1].copy(),
                        jnp.int32)
    start = np.asarray([21, 4], np.int32)
    q = jnp.asarray(rng.standard_normal((B, C, H, D)), jnp.float32)
    sel = rng.random((B, C, nb * page)) < 0.3
    sel[:, np.arange(C), start[:, None] + np.arange(C)] = True   # own row
    spec = attn_spec.AttnSpec(scale=D ** -0.5)
    twin = etap.prefill_attention_paged(
        q, pool, None, table, jnp.asarray(start),
        spec=spec.replace(use_kernels=False), dv=dv, sel=jnp.asarray(sel))
    got = etap_ops.etap_prefill_mla_paged(
        q, pool, dv, table, jnp.asarray(start),
        spec=spec.replace(interpret=True), sel=jnp.asarray(sel))
    np.testing.assert_allclose(np.asarray(got), np.asarray(twin),
                               rtol=2e-5, atol=2e-5)
    k = np.asarray(pc.gather_blocks(pool, table), np.float64)
    pos = np.arange(nb * page)
    for b in range(B):
        for c in range(C):
            live = sel[b, c] & (pos <= start[b] + c)
            s = np.asarray(q[b, c], np.float64) @ k[b].T * D ** -0.5
            s = np.where(live[None], s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            np.testing.assert_allclose(np.asarray(got[b, c]),
                                       p @ k[b][:, :dv], rtol=2e-5,
                                       atol=2e-5)


# ----------------------------------------------- against the reference
FLAGS = ["--batch", "3", "--prompt", "64", "--shared-prefix", "20",
         "--gen", "6", "--page-size", "8", "--prefill-chunk", "16",
         "--requests", "5", "--kv-dtype", "fp", "--seed", "5"]


def _serve(cfg, *extra):
    return serve.run_paged(serve.parse_args(FLAGS + list(extra)), cfg,
                           record_logits=True)


def _reference_numbers(v32, res, topk: int = TOPK):
    """(widest served gap, largest |logit error|) of the recorded rows
    against the reference at ``topk``."""
    from harness import traffic
    c = tiny_json(topk)
    a = v32.arch(c)
    flags = dict(zip(FLAGS[::2], FLAGS[1::2]))
    reqs = traffic.make_requests(flags, 5, 5, a["vocab"])
    seqs, rows = [], []
    for r in reqs:
        out = res["outputs"][r.id]
        seqs.append(np.concatenate([r.prompt, np.asarray(out[:-1],
                                                         np.int32)]))
        rows.append(np.arange(len(r.prompt) - 1, len(r.prompt) + len(out) - 1))
    ref = v32.logits(a, 5, seqs, rows, mode="fp32")
    gap = err = 0.0
    for i, r in enumerate(reqs):
        out = np.asarray(res["outputs"][r.id])
        gap = max(gap, float(np.max(ref[i].max(-1)
                                    - ref[i][np.arange(len(out)), out])))
        for j, row in ((0, res["seed_logits"][r.id]),
                       (1, res["first_decode_logits"][r.id])):
            err = max(err, float(np.max(np.abs(row - ref[i][j]))))
    return gap, err


def test_served_path_agrees_with_reference(v32):
    """Chunked prefill (prefix hits included) and paged decode against the
    reference's full forward pass, float32 on both sides: only the order of
    summation differs (~1e-6 of logits of ~0.5), and every served token is
    the reference's argmax."""
    cfg = v32.model_config(tiny_json(), "tiny")
    res = _serve(cfg)
    assert res["prefill_tokens_saved"] > 0
    counters = res["metrics"]["counters"]
    assert 0 < counters["dsa/rows_selected"] < counters["dsa/rows_scored"]
    gap, err = _reference_numbers(v32, res)
    assert gap == 0.0
    assert err < 1e-4


def test_comparison_fails_without_the_selection(v32):
    """The same comparison sees the selection: the program with its top-k
    above every context (dense attention) is far from the reference."""
    cfg = v32.model_config(tiny_json(10 ** 6), "tiny")
    res = _serve(cfg)
    assert res["metrics"]["counters"]["dsa/rows_selected"] \
        == res["metrics"]["counters"]["dsa/rows_scored"]
    _, err = _reference_numbers(v32, res)
    assert err > 1e-2


def test_run_paged_records_each_rows_selection(v32):
    """With record_logits every request's last prefill row and each decode
    row carry the positions every layer selected: min(top-k, causal rows)
    distinct causal positions; the reference's check adopts them on every
    sequence.  A configuration without an indexer records none."""
    cfg = v32.model_config(tiny_json(), "tiny")
    res = _serve(cfg)
    assert set(res["dsa_selected"]) == set(res["outputs"])
    for rid, out in res["outputs"].items():
        rec = res["dsa_selected"][rid]
        plen = len(rec["prompt"])
        assert set(range(plen - 1, plen - 1 + len(out))) <= set(rec["rows"])
        for pos, sel in rec["rows"].items():
            n = min(TOPK, pos + 1)
            assert sel.shape == (cfg.num_layers, n)
            for layer in np.asarray(sel, np.int64):
                assert len(set(layer.tolist())) == n and layer.max() <= pos
    plain = dataclasses.replace(cfg, mla=dataclasses.replace(cfg.mla,
                                                             indexer=None))
    assert _serve(plain)["dsa_selected"] == {}


# ------------------------------------------------ prefix cache, COW, swap
def test_prefix_cache_bitwise_with_dsa():
    """Served tokens and recorded logits equal bitwise with the prefix
    cache on and off; the shared prefix ends mid-page, so the cached run
    copies a block (COW) of both pools."""
    cfg = dataclasses.replace(dsa_cfg(), moe=None)
    base = ["--reduced", "--batch", "1", "--prompt", "40", "--gen", "4",
            "--requests", "3", "--page-size", "8", "--prefill-chunk", "4",
            "--shared-prefix", "28", "--kv-dtype", "fp"]
    on = serve.run_paged(serve.parse_args(base), cfg, record_logits=True)
    off = serve.run_paged(serve.parse_args(base + ["--no-prefix-cache"]),
                          cfg, record_logits=True)
    assert on["outputs"] == off["outputs"]
    assert on["prefill_tokens_saved"] > 0
    for k, v in off["first_decode_logits"].items():
        np.testing.assert_array_equal(on["first_decode_logits"][k], v)


def test_cow_and_swap_carry_index_keys():
    cfg = dsa_cfg()
    layout = pc.layout_for(2, 32, block_size=8)
    progs = serve.paged_programs(cfg, layout, attn_spec.AttnSpec())
    cache = progs.init()
    assert set(cache[0]["b0"]) == {"c", "k_idx"}
    rng = np.random.default_rng(6)
    cache = jax.tree.map(lambda a: jnp.asarray(
        rng.standard_normal(a.shape), a.dtype), cache)
    before = jax.tree.map(np.asarray, cache)
    c1 = progs.copy_block(cache, 1, 3)
    rows = model.read_paged_blocks(c1, [1, 3])
    c2 = progs.write_blocks(c1, np.asarray([4, 5], np.int32), rows)
    for old, new in zip(jax.tree.leaves(before), jax.tree.leaves(c2),
                        strict=True):
        new = np.asarray(new)
        np.testing.assert_array_equal(new[:, 3], old[:, 1])
        np.testing.assert_array_equal(new[:, 4], old[:, 1])
        np.testing.assert_array_equal(new[:, 5], old[:, 1])


# ------------------------------------------ the programs without indexer
def _vmapped_init(rng, cfg):
    """The stacked draw model.init made before it drew layer by layer."""
    dtype = cfg.jax_dtype
    keys = jax.random.split(rng, len(model.layer_groups(cfg)) + 2)
    out = []
    for g, key in zip(model.layer_groups(cfg), keys[2:], strict=True):
        def one(k, g=g):
            ks = jax.random.split(k, len(g["sigs"]))
            return {f"b{j}": model._init_block(ks[j], cfg, s, dtype)
                    for j, s in enumerate(g["sigs"])}
        out.append(jax.vmap(one)(jax.random.split(key, g["n"])))
    return out


@pytest.mark.parametrize("first_dense", [3, 1])
def test_layerwise_init_draws_bitwise_as_the_stacked_draw(first_dense):
    """R1's (3 dense + 1 MoE) and Kimi-K2's (1 dense + 3 MoE) layer
    patterns in bfloat16: every leaf of the layer-by-layer draw equals the
    stacked vmapped draw bit for bit."""
    cfg = reduced(get_config("deepseek_r1_671b"), layers=4)
    cfg = dataclasses.replace(cfg, dtype="bfloat16", moe=dataclasses.replace(
        cfg.moe, first_dense_layers=first_dense))
    got = model.init(jax.random.PRNGKey(7), cfg)["groups"]
    want = _vmapped_init(jax.random.PRNGKey(7), cfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a).view(np.uint8),
                                      np.asarray(b).view(np.uint8))


def test_r1_shape_builds_no_indexer_state():
    """Without an indexer the parameters and the paged cache hold exactly
    what they held before: no index weights, no index-key pool, and the
    indexer's weights of a DSA config leave every other weight as drawn."""
    cfg = dataclasses.replace(dsa_cfg(), mla=dataclasses.replace(
        dsa_cfg().mla, indexer=None))
    p = model.init(jax.random.PRNGKey(0), cfg)
    q = model.init(jax.random.PRNGKey(0), dsa_cfg())
    assert "idx" not in p["groups"][0]["b0"]["mix"]
    assert set(q["groups"][0]["b0"]["mix"]) - set(
        p["groups"][0]["b0"]["mix"]) == {"idx"}
    flat = dict(jax.tree_util.tree_flatten_with_path(p)[0])
    for path, leaf in jax.tree_util.tree_flatten_with_path(q)[0]:
        if "idx" not in jax.tree_util.keystr(path):
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(flat[path]))
    layout = pc.layout_for(2, 32, block_size=8)
    assert set(model.init_paged_cache(cfg, layout)[0]["b0"]) == {"c"}
    # the latent row is stored lane-aligned (40 -> 128), the index key as is
    assert model.paged_row_bytes(cfg) == 3 * 128 * 4
    assert model.paged_row_bytes(dsa_cfg()) == 3 * (128 + 16) * 4


@pytest.mark.parametrize("extra", [["--kv-dtype", "int8"],
                                   ["--kv-dtype", "fp8"],
                                   ["--spec-tokens", "2"]])
def test_run_paged_refuses_what_carries_no_index_keys(extra):
    args = serve.parse_args(["--reduced", "--batch", "1", "--prompt", "8",
                             "--gen", "2", "--requests", "1", "--kv-dtype",
                             "fp"] + extra)
    with pytest.raises(ValueError, match="DeepSeek Sparse Attention"):
        serve.run_paged(args, dsa_cfg())
