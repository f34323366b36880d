"""Paged KV-cache validation (DESIGN.md §8): paged-vs-dense oracle
equivalence across block sizes / split counts / ragged lengths straddling
block boundaries, the bitwise dense↔paged contract at block-aligned
lengths, allocator reuse-after-release + out-of-blocks admission refusal,
and the continuous-batching serve loop end to end.  All Pallas runs are
interpret=True on CPU; tolerances match tests/test_splitkv.py."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.etap import decode_attention_paged, etap_decode_paged_xla
from repro.kernels.etap import ops as etap_ops
from repro.kernels.etap.ref import etap_decode_ref
from repro.kernels.etap.schedule import paged_split_geometry, plan_splits_paged
from repro.runtime import paged_cache as pc

RNG = np.random.default_rng(11)


def _mk(B, H, Dk, Dv, S, *, lengths):
    q = jnp.asarray(RNG.normal(size=(B, H, Dk)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, Dk)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, Dv)), jnp.float32)
    return q, k, v, jnp.asarray(lengths, jnp.int32)


def _paged(dense, lengths, page, *, spare=4):
    layout = pc.layout_for(dense.shape[0], dense.shape[1], block_size=page,
                           spare_blocks=spare)
    pool, bp = pc.dense_to_paged(dense, np.asarray(lengths), layout)
    return pool, bp


def _rmse(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2)))


# lengths deliberately straddle page boundaries for both page sizes:
# one mid-page, one exactly on a 16-boundary, one one-past-a-64-boundary,
# one at the full context.
S = 320
RAGGED = [7, 64, 65, 320]


@pytest.mark.parametrize("page", [16, 64])
@pytest.mark.parametrize("n_splits", [1, 4])
def test_paged_separate_v_vs_ref(page, n_splits):
    q, k, v, L = _mk(4, 8, 64, 64, S, lengths=RAGGED)
    scale = 64 ** -0.5
    ref = etap_decode_ref(q, k, v, L, scale=scale)
    k_pool, bp = _paged(k, RAGGED, page)
    v_pool, _ = _paged(v, RAGGED, page)
    table, lengths = bp.device_views()
    out = etap_ops.etap_decode_paged_splitkv(q, k_pool, v_pool, table,
                                             lengths, scale=scale,
                                             n_splits=n_splits)
    assert _rmse(out, ref) <= 1e-4
    # same geometry through the gather-based XLA path
    out_x = etap_decode_paged_xla(q, k_pool, v_pool, table, lengths,
                                  scale=scale)
    assert _rmse(out_x, ref) <= 1e-4


@pytest.mark.parametrize("page", [16, 64])
@pytest.mark.parametrize("n_splits", [1, 4])
def test_paged_mla_fused_vs_ref(page, n_splits):
    q, kv, _, L = _mk(4, 8, 96, 96, S, lengths=RAGGED)
    dv = 64                                  # V = first 64 latent columns
    scale = 96 ** -0.5
    ref = etap_decode_ref(q, kv, kv[..., :dv], L, scale=scale)
    kv_pool, bp = _paged(kv, RAGGED, page)
    table, lengths = bp.device_views()
    out = etap_ops.etap_decode_mla_paged_splitkv(q, kv_pool, dv, table,
                                                 lengths, scale=scale,
                                                 n_splits=n_splits)
    assert _rmse(out, ref) <= 1e-4


@pytest.mark.parametrize("page", [16, 64])
def test_paged_bitwise_dense_at_block_aligned(page):
    """At block-aligned lengths with n_splits=1, the paged kernel walks the
    same blocks in the same order as the dense kernel at block == page —
    the block table only redirects the DMA source, so outputs are BITWISE
    equal (acceptance criterion)."""
    aligned = [page, 2 * page, 4 * page, S]
    q, k, v, L = _mk(4, 8, 64, 64, S, lengths=aligned)
    scale = 64 ** -0.5
    k_pool, bp = _paged(k, aligned, page)
    v_pool, _ = _paged(v, aligned, page)
    table, lengths = bp.device_views()
    dense = etap_ops.etap_decode(q, k, v, L, scale=scale, block=page)
    paged = etap_ops.etap_decode_paged_splitkv(q, k_pool, v_pool, table,
                                               lengths, scale=scale,
                                               n_splits=1)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))
    # and the gather-based XLA paged path is bitwise the dense XLA loop
    from repro.core.etap import etap_decode_xla
    np.testing.assert_array_equal(
        np.asarray(etap_decode_paged_xla(q, k_pool, v_pool, table, lengths,
                                         scale=scale)),
        np.asarray(etap_decode_xla(q, k, v, L, scale=scale, block=page)))


def test_paged_shuffled_table_matches_logical_order():
    """The kernels must follow the TABLE, not physical pool order: serve a
    sequence whose blocks are deliberately scattered through the pool."""
    page, n = 16, 8
    q, k, v, L = _mk(1, 8, 64, 64, n * page, lengths=[n * page])
    scale = 64 ** -0.5
    perm = RNG.permutation(np.arange(1, n + 1)).astype(np.int32)
    pool_k = np.zeros((n + 1, page, 64), np.float32)
    pool_v = np.zeros((n + 1, page, 64), np.float32)
    pool_k[perm] = np.asarray(k[0]).reshape(n, page, 64)
    pool_v[perm] = np.asarray(v[0]).reshape(n, page, 64)
    out = etap_ops.etap_decode_paged(q, jnp.asarray(pool_k),
                                     jnp.asarray(pool_v), perm[None, :],
                                     L, scale=scale)
    ref = etap_decode_ref(q, k, v, L, scale=scale)
    assert _rmse(out, ref) <= 1e-4


def test_decode_attention_paged_modes_agree():
    """Unified paged entry point: kernel / XLA / standard-baseline paths
    agree on the same paged cache (ragged lengths)."""
    q, k, v, L = _mk(3, 8, 64, 32, 256, lengths=[5, 128, 250])
    scale = 64 ** -0.5
    ref = etap_decode_ref(q, k, v, L, scale=scale)
    k_pool, bp = _paged(k, [5, 128, 250], 64)
    v_pool, _ = _paged(v, [5, 128, 250], 64)
    table, lengths = bp.device_views()
    for kw in (dict(mode="etap", use_kernels=True),
               dict(mode="etap", use_kernels=False),
               dict(mode="etap", use_kernels=False, n_splits=4),
               dict(mode="standard", use_kernels=False)):
        out = decode_attention_paged(q, k_pool, v_pool, table, lengths,
                                     scale=scale, **kw)
        assert _rmse(out, ref) <= 1e-4, kw


# ---------------------------------------------------------------- allocator
def test_allocator_reuse_after_release():
    layout = pc.PagedLayout(block_size=16, num_blocks=7, max_blocks=3)
    bp = pc.BlockPool(layout, 2)
    s0 = bp.admit(40, 48)                    # 3 blocks
    s1 = bp.admit(30, 48)                    # 3 blocks -> pool exhausted
    assert s0 == 0 and s1 == 1 and bp.num_free == 0
    ids0 = set(bp.block_ids(s0))
    bp.release(s0)
    assert bp.num_free == 3
    assert not bp.active[s0]
    assert (bp.table[s0] == pc.NULL_BLOCK).all()
    s2 = bp.admit(10, 48)
    assert s2 == s0                          # slot recycled
    assert set(bp.block_ids(s2)) == ids0     # blocks recycled
    # no double allocation: s1 and s2 own disjoint blocks
    assert not (set(bp.block_ids(s1)) & set(bp.block_ids(s2)))


def test_allocator_out_of_blocks_admission_refusal():
    layout = pc.PagedLayout(block_size=16, num_blocks=5, max_blocks=4)
    bp = pc.BlockPool(layout, 4)
    assert bp.admit(48, 48) == 0             # takes 3 of 4 blocks
    assert not bp.can_admit(32)              # 2 blocks needed, 1 free
    assert bp.admit(20, 32) is None          # refusal, not an error
    assert bp.admit(70, 70) is None          # > max_len always refused
    assert bp.admit(9, 16) == 1              # 1 block still fits
    bp.release(0)
    assert bp.can_admit(48)                  # refusal clears after release


def test_shared_admission_midblock_cow_refusal_boundary():
    """ISSUE 5 satellite: when a shared prefix ends MID-block, the chain's
    partial tail block is NOT mapped — its logical position needs a fresh
    eager-COW copy target, which must be charged to the free list BEFORE
    admission succeeds.  At exactly-one-block-short occupancy the
    accounting must refuse; counting ``len(chain)`` as shared (the old
    serve-loop arithmetic) would say yes here and strand the request
    between a lying can_admit and a refusing admit_shared."""
    bs = 16
    # donor chain: 3 blocks holding 40 tokens (third block PARTIAL at 8)
    layout = pc.PagedLayout(block_size=bs, num_blocks=1 + 3 + 2,
                            max_blocks=5)
    bp = pc.BlockPool(layout, 3)
    donor = bp.admit(40, 40)
    chain = [int(b) for b in bp.block_ids(donor)]
    assert len(chain) == 3
    # new request: same 40-token prefix + budget to 64 tokens = 4 logical
    # blocks; 2 full shared blocks map, so it needs 4 - 2 = 2 fresh blocks
    # (one of them the COW copy of the partial third block) but only 2
    # remain... take one away to sit exactly one block short.
    filler = bp.admit(bs, bs)                # consumes 1 block -> 1 free
    n_full = 40 // bs                        # 2 FULL shared blocks
    assert not bp.can_admit(64, n_shared=n_full)       # 2 needed, 1 free
    # the buggy arithmetic (len(chain) == 3 shared) would claim it fits:
    assert bp.can_admit(64, n_shared=len(chain))
    # and admit_shared, which counts full blocks itself, refuses — the
    # predicate and the admission must agree at the boundary
    assert bp.admit_shared(40, 64, chain) is None
    bp.check_conservation()
    # with the missing block back, the same admission succeeds and returns
    # the (partial donor block -> fresh private block) COW pair
    bp.release(filler)
    assert bp.can_admit(64, n_shared=n_full)
    slot, cow = bp.admit_shared(40, 64, chain)
    assert len(cow) == 1
    src, dst = cow[0]
    assert src == chain[2] and dst not in chain
    # the mapped prefix shares refcounts; the COW target is private
    assert all(int(bp.ref[b]) == 2 for b in chain[:2])
    assert int(bp.ref[src]) == 1 and int(bp.ref[dst]) == 1
    bp.check_conservation()


def test_append_rows_across_block_boundary():
    """Token-by-token appends crossing a page boundary land in the right
    (block, slot) cells; inactive slots write only the null block."""
    layout = pc.PagedLayout(block_size=4, num_blocks=6, max_blocks=2)
    bp = pc.BlockPool(layout, 2)
    slot = bp.admit(3, 8)
    assert slot == 0                         # slot 1 stays inactive
    pool = jnp.zeros((6, 4, 2))
    ref = np.zeros((8, 2), np.float32)
    for t in range(3, 8):
        table, lengths = bp.device_views()
        row = jnp.full((2, 2), float(t))
        pool = pc.append_rows(pool, table, lengths, row)
        ref[t] = t
        bp.append(0)
    dense = pc.gather_blocks(pool, bp.device_views()[0])
    np.testing.assert_array_equal(np.asarray(dense[0]), ref)
    # slot 1 (inactive, all-null table) only ever wrote the null block:
    # every block that is neither null nor owned by slot 0 is untouched
    untouched = sorted(set(range(6)) - {pc.NULL_BLOCK}
                       - set(bp.block_ids(0).tolist()))
    np.testing.assert_array_equal(np.asarray(pool[np.asarray(untouched)]),
                                  np.zeros((len(untouched), 4, 2)))


def test_release_nulls_whole_row_and_is_unreachable_from_device_views():
    """Release audit: a released slot's table row is fully nulled at ROW
    granularity — no stale physical id at any column — so no stale mapping
    can reach a kernel through device_views(); and device views taken
    BEFORE the release are copies, immune to the mutation."""
    layout = pc.PagedLayout(block_size=4, num_blocks=8, max_blocks=4)
    bp = pc.BlockPool(layout, 2)
    s = bp.admit(10, 12)                     # 3 of 4 table columns used
    table_before, _ = bp.device_views()
    assert (np.asarray(table_before[s][:3]) != pc.NULL_BLOCK).all()
    bp.release(s)
    assert (bp.table[s] == pc.NULL_BLOCK).all()
    table, lengths = bp.device_views()
    assert (np.asarray(table[s]) == pc.NULL_BLOCK).all()
    assert int(lengths[s]) == 0
    # a view taken pre-release is an owned copy: still the old ids (the
    # async-dispatch contract), while the live table shows only nulls
    assert (np.asarray(table_before[s][:3]) != pc.NULL_BLOCK).all()
    bp.check_conservation()


# ---------------------------------------------------------------- scheduler
def test_paged_split_geometry_page_granular():
    for nb in (1, 3, 7, 16):
        for n in (1, 2, 4, 8):
            n_eff, npb, padded = paged_split_geometry(nb, n)
            assert padded % n_eff == 0 and padded >= nb
            assert npb * n_eff == padded
            # effective count: every split owns >= 1 REAL table column
            assert 1 <= n_eff <= min(n, nb)
            assert (n_eff - 1) * npb < nb
    plan = plan_splits_paged(1, 1024, 64, 16, 512)
    assert plan.block == 64                  # split unit is the page
    assert plan.n_splits * plan.nb_per_split >= 1024   # plan covers the table
    # long context / small batch does split; page-sized context doesn't
    assert plan.n_splits > 1
    assert plan_splits_paged(16, 1, 64, 16, 512).n_splits == 1


# ------------------------------------------------------------ end to end
def test_decode_step_paged_matches_dense():
    """cache_layout="paged" is a layout change, not a model change:
    teacher-forced per-step logits match the dense path to float-noise
    tolerance on the same prompts (reduced deepseek — the paper's arch).
    Teacher-forced because greedy streams amplify near-tie argmax flips
    between summation orders into different suffixes; MoE is dropped
    because the top-k router is DISCONTINUOUS — float-noise differences
    between the two layouts' summation orders can flip an expert at a
    near-tie gate and produce an O(1e-2) logit jump that has nothing to do
    with the cache layout under test.

    Under REPRO_KV_DTYPE=int8/fp8 (the CI quantized leg) the paged cache
    stores codes, so the comparison against the fp dense path loosens to
    the layout's measured quantization-error budget instead of float
    noise — the test then proves the quantized serving path tracks the fp
    model, not that it equals it."""
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.models import model

    kv_dtype = os.environ.get("REPRO_KV_DTYPE", "fp")
    atol = {"fp": 1e-4, "int8": 0.05, "fp8": 0.2}[kv_dtype]
    cfg = dataclasses.replace(reduced(get_config("deepseek_r1_671b")),
                              moe=None)
    params = model.init(jax.random.PRNGKey(0), cfg)
    B, S, GEN = 2, 24, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    forced = jax.random.randint(jax.random.PRNGKey(2), (GEN, B), 0,
                                cfg.vocab_size)
    _, cache, pos = model.prefill(params, cfg, {"tokens": toks},
                                  max_len=S + GEN)
    dense_lg = []
    for i in range(GEN):
        lg, cache = model.decode_step(params, cfg, cache, forced[i],
                                      pos + i, kv_splits=1)
        dense_lg.append(lg)

    layout = pc.layout_for(B, S + GEN, block_size=16)
    bp = pc.BlockPool(layout, B)
    paged = model.init_paged_cache(cfg, layout, kv_dtype=kv_dtype)
    for b in range(B):
        slot = bp.admit(0, S + GEN)          # cold: chunked prefill fills it
        assert slot == b
    for lo, hi in ((0, 16), (16, S)):        # aligned + unaligned chunks
        table, lengths = bp.device_views()
        _, paged = model.prefill_chunk(params, cfg, paged, toks[:, lo:hi],
                                       table, lengths)
        for b in range(B):
            bp.extend(b, hi - lo)
    for i in range(GEN):
        table, lengths = bp.device_views()
        lg, paged = model.decode_step(params, cfg, paged, forced[i], None,
                                      kv_splits=1, cache_layout="paged",
                                      block_table=table, lengths=lengths)
        for b in range(B):
            bp.append(b)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(dense_lg[i]),
                                   atol=atol, rtol=1e-4)


def test_init_paged_cache_rejects_non_attention():
    from repro.configs import get_config, reduced
    from repro.models import model
    cfg = reduced(get_config("falcon_mamba_7b"))
    with pytest.raises(ValueError, match="attention-only"):
        model.init_paged_cache(cfg, pc.PagedLayout(16, 4, 2))


def test_continuous_batching_serve_loop():
    """Ragged requests join and leave the batch; every request gets exactly
    its budgeted tokens; throughput accounting counts true tokens served
    (NOT batch * gen); out-of-pool requests wait, none are dropped."""
    from repro.launch import serve

    args = serve.parse_args([
        "--reduced", "--batch", "2", "--prompt", "24", "--gen", "6",
        "--requests", "5", "--page-size", "8", "--cache-layout", "paged"])
    res = serve.run(args)
    assert len(res["outputs"]) == 5          # every request served
    gens = {i: len(v) for i, v in res["outputs"].items()}
    assert res["tokens_served"] == sum(gens.values())
    assert all(n in (3, 6) for n in gens.values())  # the two gen buckets
    # ragged stream through the slots must beat the naive fixed-batch
    # count (batch_slots: quantized layouts admit MORE than --batch under
    # the same byte budget, so the reported count is the bound)
    assert res["steps"] >= max(gens.values())
    assert res["tokens_served"] <= res["batch_slots"] * res["steps"]


# ---------------------------------------------- the pool carried whole
def _isolation_cfg(case):
    """(config, kv layout) of a layer-isolation case: MLA in bf16 with a
    lone dense layer and a scanned group of 3 MoE layers, the same stack
    with int8 codes + "c_sz" scale pools, and a 3-layer GQA k/v stack."""
    import dataclasses

    from repro.configs import get_config, reduced
    if case == "gqa":
        return dataclasses.replace(reduced(get_config("qwen3_8b"),
                                           kv_heads=2), num_layers=3), "fp"
    cfg = dataclasses.replace(reduced(get_config("deepseek_r1_671b")),
                              num_layers=4)
    if case == "mla-bf16":
        return dataclasses.replace(cfg, dtype="bfloat16"), "fp"
    return cfg, "int8"


def _distinct_pools(cache, seed):
    """Random values in every leaf, so each layer's pool differs from every
    other layer's everywhere (scales and zero-points stay positive)."""
    rng = np.random.default_rng(seed)

    def fill(a):
        if jnp.issubdtype(a.dtype, jnp.integer):
            return jnp.asarray(rng.integers(-100, 100, a.shape), a.dtype)
        return jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype)
    return jax.tree.map(fill, cache)


def _assert_rows_written(old, new, table, starts, n_rows):
    """Every layer's pool changed exactly at the rows each batch row wrote
    through the shared table (n_rows tokens from starts[b]), in its own
    pool: an all-null table row writes its own layer's null block."""
    page = jax.tree.leaves(old)[0].shape[2]
    n_blocks = jax.tree.leaves(old)[0].shape[1]
    want = np.zeros((n_blocks, page), bool)
    for b, s in enumerate(starts):
        for t in range(s, s + n_rows):
            want[table[b, t // page], t % page] = True
    for g, (g_old, g_new) in enumerate(zip(old, new, strict=True)):
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_old),
                                jax.tree.leaves(g_new), strict=True):
            o = np.asarray(a).astype(np.float32)
            n = np.asarray(b).astype(np.float32)
            for i in range(o.shape[0]):
                moved = (o[i] != n[i]).reshape(n_blocks, page, -1).any(-1)
                where = f"group {g} layer {i} {jax.tree_util.keystr(path)}"
                assert not (moved & ~want).any(), \
                    f"{where} changed rows it never wrote: " \
                    f"{np.argwhere(moved & ~want)[:4].tolist()}"
                assert moved[want].all(), \
                    f"{where} missed rows it wrote: " \
                    f"{np.argwhere(want & ~moved)[:4].tolist()}"


@pytest.mark.parametrize("case", ["mla-bf16", "mla-int8", "gqa"])
def test_paged_programs_write_only_their_own_layer(case):
    """The paged decode step, prefill chunk and verify step carry each layer
    group's pool as one flat view and address layer i through
    table + i*N: each layer's pool changes only at the rows that layer
    wrote, and an inactive slot (all-null table row) writes only into its
    own layer's null block.  Chunk rows straddle block boundaries."""
    from repro.core import attn_spec
    from repro.models import model

    cfg, kv_dtype = _isolation_cfg(case)
    params = model.init(jax.random.PRNGKey(0), cfg)
    layout = pc.layout_for(3, 32, block_size=8, spare_blocks=2)
    bp = pc.BlockPool(layout, 3)
    for b, n in ((0, 13), (1, 5)):          # slot 2 stays inactive
        assert bp.admit(0, 32) == b
        bp.extend(b, n)
    table, lengths = bp.device_views()
    host_table, starts = np.asarray(table), np.asarray(lengths)
    assert not host_table[2].any() and starts[2] == 0
    cache = _distinct_pools(
        model.init_paged_cache(cfg, layout, kv_dtype=kv_dtype), seed=1)
    spec = attn_spec.AttnSpec(kv_dtype=kv_dtype)
    toks = jax.random.randint(jax.random.PRNGKey(2), (3, 6), 0,
                              cfg.vocab_size)

    _, new = model.decode_step(params, cfg, cache, toks[:, 0], None,
                               spec=spec, cache_layout="paged",
                               block_table=table, lengths=lengths)
    _assert_rows_written(cache, new, host_table, starts, 1)
    _, new = model.prefill_chunk(params, cfg, cache, toks, table, lengths,
                                 spec=spec)            # 13..18 crosses 16
    _assert_rows_written(cache, new, host_table, starts, 6)
    _, new = model.verify_step(params, cfg, cache, toks[:, :3], table,
                               lengths, spec=spec)
    _assert_rows_written(cache, new, host_table, starts, 3)


def test_pool_format_survives_every_writer():
    """init -> decode step -> COW copy -> swap write -> decode step through
    launch/serve.paged_programs: every program takes the pool in the format
    the serve entries were compiled for, hands it back in that format, and
    consumes (donates) its input, so the pool is updated in place and no
    program relays it out or compiles again for another layout."""
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.core import attn_spec
    from repro.launch import serve
    from repro.models import model

    cfg = dataclasses.replace(reduced(get_config("deepseek_r1_671b")),
                              num_layers=4)
    params = model.init(jax.random.PRNGKey(0), cfg)
    layout = pc.layout_for(2, 32, block_size=8)
    progs = serve.paged_programs(cfg, layout, attn_spec.AttnSpec())
    bp = pc.BlockPool(layout, 2)
    assert bp.admit(0, 32) == 0
    table, lengths = bp.device_views()
    tok = jnp.zeros((2,), jnp.int32)
    cache = progs.init()
    step_in = progs.step.lower(params, cache, tok, table,
                               lengths).compile().input_formats[0][1]

    def formats(c):
        return jax.tree.map(lambda a: a.format, c)

    def check(before, after):
        assert formats(after) == step_in
        assert all(a.is_deleted() for a in jax.tree.leaves(before))

    assert formats(cache) == step_in
    _, c1 = progs.step(params, cache, tok, table, lengths)
    check(cache, c1)
    c2 = progs.copy_block(c1, 1, 3)
    check(c1, c2)
    rows = model.read_paged_blocks(c2, [1, 3])
    c3 = progs.write_blocks(c2, np.asarray([4, 5], np.int32), rows)
    check(c2, c3)
    for leaf, row in zip(jax.tree.leaves(c3), jax.tree.leaves(rows),
                         strict=True):
        np.testing.assert_array_equal(np.asarray(leaf[:, 4:6]), row)
    _, c4 = progs.step(params, c3, tok, table, lengths)
    check(c3, c4)


# ------------------------------------------- the lane-aligned latent pool
LAT, DV, WIDE = 576, 512, pc.lane_width(576)     # R1's latent, V, 640


def test_lane_width_rounds_up_to_whole_lane_tiles():
    assert [pc.lane_width(d) for d in (40, 128, 576, 640, 641)] == \
        [128, 128, 640, 640, 768]
    x = jnp.ones((2, 3, 40), jnp.bfloat16)
    y = pc.pad_lanes(x, 128)
    assert y.shape == (2, 3, 128) and y.dtype == x.dtype
    assert not np.asarray(y[..., 40:], np.float32).any()
    assert pc.pad_lanes(x, 40) is x


def _mla_stack(kv_dtype="fp", **over):
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.models import model
    cfg = dataclasses.replace(reduced(get_config("deepseek_r1_671b")),
                              num_layers=2, moe=None, **over)
    layout = pc.layout_for(2, 32, block_size=8, spare_blocks=3)
    return cfg, layout, model.init_paged_cache(cfg, layout,
                                               kv_dtype=kv_dtype)


def _padding(cache, latent):
    """Every value in the padded lanes of every latent code pool."""
    return np.concatenate([np.asarray(g[k]["c"][..., latent:], np.float32)
                           .ravel() for g in cache for k in g])


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_padded_lanes_stay_zero_through_every_writer(kv_dtype):
    """The latent pool is stored lane_width(latent) wide; the decode row,
    the prefill chunk and the verify rows, the COW block copy and a swap
    out and back all leave its padded lanes exactly 0 (code 0 in a
    quantized pool)."""
    from repro.core import attn_spec
    from repro.models import model

    cfg, layout, cache = _mla_stack(kv_dtype)
    latent = cfg.mla.latent_dim
    for g in cache:
        for blk in g.values():
            assert blk["c"].shape[-1] == pc.lane_width(latent) > latent
    params = model.init(jax.random.PRNGKey(0), cfg)
    spec = attn_spec.AttnSpec(kv_dtype=kv_dtype)
    bp = pc.BlockPool(layout, 2)
    for b in range(2):
        assert bp.admit(0, 32) == b
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 11), 0,
                              cfg.vocab_size)
    table, lengths = bp.device_views()
    _, cache = model.prefill_chunk(params, cfg, cache, toks[:, :9], table,
                                   lengths, spec=spec)   # crosses a page
    for b in range(2):
        bp.extend(b, 9)
    table, lengths = bp.device_views()
    _, cache = model.decode_step(params, cfg, cache, toks[:, 9], None,
                                 spec=spec, cache_layout="paged",
                                 block_table=table, lengths=lengths)
    _, cache = model.verify_step(params, cfg, cache, toks[:, 9:], table,
                                 lengths, spec=spec)
    first, second = (int(i) for i in bp.block_ids(0)[:2])
    cache = model.copy_paged_block(cache, first, layout.num_blocks - 1)
    rows = model.read_paged_blocks(cache, [first, second])   # swap out
    cache = model.write_paged_blocks(cache, np.asarray([5, 6], np.int32),
                                     rows)                   # and back
    written = np.asarray(cache[0]["b0"]["c"][0, first], np.float32)
    assert written[:, :latent].any()
    assert not _padding(cache, latent).any()


def _lane_case(path, use_kernels):
    """One attention call on a 576-wide pool and the same call on the
    640-wide pool that holds the same rows, with q zero-extended: the
    paged decode, the chunked prefill, the verify and the DSA sparse
    decode (selection included), on the Pallas kernel in interpret mode or
    the XLA twin."""
    from repro.core import attn_spec
    from repro.core import etap as core

    rng = np.random.default_rng(5)
    spec = attn_spec.AttnSpec(scale=192 ** -0.5, use_kernels=use_kernels)
    page, lens = 64, [200, 131]
    dense = jnp.asarray(rng.normal(size=(2, 256, LAT)), jnp.bfloat16)
    pool, bp = _paged(dense, lens, page, spare=0)
    table, lengths = bp.device_views()
    wide = pc.pad_lanes(pool, WIDE)

    def q_of(shape):
        q = jnp.asarray(rng.normal(size=shape + (LAT,)), jnp.bfloat16)
        return q, pc.pad_lanes(q, WIDE)

    if path == "decode":
        q, qw = q_of((2, 16))
        return [core.decode_attention_paged(qq, p, None, table, lengths,
                                            spec=spec, dv=DV)
                for qq, p in ((q, pool), (qw, wide))]
    if path in ("prefill", "verify"):
        q, qw = q_of((2, 8, 4))
        start = lengths - 8
        qpos = start[:, None] + jnp.asarray([0, 1, 2, 2, 4, 5, 5, 7])
        if path == "prefill":
            return [core.prefill_attention_paged(qq, p, None, table, start,
                                                 spec=spec, dv=DV)
                    for qq, p in ((q, pool), (qw, wide))]
        return [core.verify_attention_paged(qq, p, None, table, start, qpos,
                                            spec=spec, dv=DV)
                for qq, p in ((q, pool), (qw, wide))]
    q, qw = q_of((2, 16))
    kdense = jnp.asarray(rng.normal(size=(2, 256, 128)), jnp.bfloat16)
    kpool, _ = _paged(kdense, lens, page, spare=0)
    q_idx = jnp.asarray(rng.normal(size=(2, 8, 128)), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(size=(2, 8)), jnp.float32)
    return [core.dsa_decode_paged(qq, p, kpool, q_idx, w, table, lengths,
                                  spec=spec, dv=DV, topk=128)
            for qq, p in ((q, pool), (qw, wide))]


@pytest.mark.parametrize("use_kernels", [True, False],
                         ids=["pallas", "xla"])
@pytest.mark.parametrize("path", ["decode", "prefill", "verify", "dsa"])
def test_wide_pool_is_bitwise_the_narrow_one(path, use_kernels):
    narrow, wide = _lane_case(path, use_kernels)
    for a, b in zip(jax.tree.leaves(narrow), jax.tree.leaves(wide),
                    strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a).view(np.uint8),
                                      np.asarray(b).view(np.uint8))


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quantized_rows_in_a_wide_pool(kv_dtype):
    """A quantized row's (scale, zp) comes from its 576 real lanes alone,
    its codes there are those a 576-wide pool holds, and the padded codes
    are 0: finite once dequantized, and met only by zero query lanes."""
    if kv_dtype == "fp8" and not pc.HAS_FP8:
        pytest.skip("no float8_e4m3fn in this jax build")
    rng = np.random.default_rng(9)
    layout = pc.layout_for(2, 64, block_size=16)
    bp = pc.BlockPool(layout, 2)
    for b in range(2):
        assert bp.admit(0, 64) == b
        bp.extend(b, 13 + 20 * b)
    table, lengths = bp.device_views()
    dt = pc.quant_dtype(kv_dtype)
    sz0 = jnp.zeros((layout.num_blocks, 16, 2), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(2, 5, LAT)) * 3, jnp.bfloat16)
    out = {}
    for width in (LAT, WIDE):
        pool = jnp.zeros((layout.num_blocks, 16, width), dt)
        pool, sz = pc.append_chunk_quant(pool, sz0, table, lengths, rows)
        out[width] = pc.append_rows_quant(pool, sz, table, lengths + 5,
                                          rows[:, 0])
    (narrow, sz_n), (wide, sz_w) = out[LAT], out[WIDE]
    view = np.uint8
    np.testing.assert_array_equal(np.asarray(wide[..., :LAT]).view(view),
                                  np.asarray(narrow).view(view))
    assert not np.asarray(wide[..., LAT:]).view(view).any()
    np.testing.assert_array_equal(np.asarray(sz_w), np.asarray(sz_n))
    assert np.isfinite(np.asarray(pc.dequantize_rows(wide, sz_w))).all()


def test_run_paged_reports_a_row_major_pool():
    """run_paged reads the pool's device format once, at creation, into
    the paged/pool_row_major gauge (1 on the CPU, whose default layout is
    row-major at every width)."""
    from repro.launch import serve
    args = serve.parse_args(["--reduced", "--batch", "1", "--prompt", "8",
                             "--gen", "2", "--requests", "1"])
    res = serve.run(args)
    assert res["metrics"]["gauges"]["paged/pool_row_major"] == 1
