"""Compile the serve path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel entry (or the whole jitted paged
decode step) at DeepSeek-R1 chip-share widths — B 16, H 16, latent 576
stored 640 lanes wide, Dv 512, page 64, bf16 — for one chip of a
``v5e:2x2`` topology, and asserts the compiled program holds a Mosaic
kernel (``tpu_custom_call``).  Mosaic's block-tiling rule and its VMEM
budget are enforced here, where interpret mode enforces neither.  The
serve programs of ``launch/serve.paged_programs`` are compiled as the
serve loop jits them: their compiled text must hold no whole-pool copy,
the chip's default layout of every pool they carry must be row-major,
and their pool formats must agree from one program to the next.

The topology is described inside a module fixture (never at import):
only one process may hold the TPU library, and xdist workers all import
this file.  The persistent compilation cache is off around these
compiles — an entry written for a described chip cannot be read back.
"""
from __future__ import annotations

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import attn_spec
from repro.kernels.etap import ops
from repro.launch import serve
from repro.models import model
from repro.runtime.paged_cache import lane_width, layout_for

B, H, LAT, DV, PAGE, NB = 16, 16, 576, 512, 64, 64
W = lane_width(LAT)                     # the paged pool's stored row width
N_BLOCKS = 1 + B * NB
SPEC = attn_spec.AttnSpec(scale=192 ** -0.5, interpret=False)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip from (shape, dtype) pairs or
    shape pytrees; returns the compiled program's text."""
    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    args = [jax.tree.map(place, s) if not isinstance(s, tuple)
            else place(jax.ShapeDtypeStruct(*s)) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _pool(dtype=jnp.bfloat16):
    return (N_BLOCKS, PAGE, W), dtype


TABLE = ((B, NB), jnp.int32)
LENS = ((B,), jnp.int32)
SZ = ((N_BLOCKS, PAGE, 2), jnp.float32)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_mla_decode(one_chip, kv):
    q = ((B, H, W), jnp.bfloat16)
    if kv == "int8":
        text = _compile(
            lambda q, p, sz, t, n: ops.etap_decode_mla_paged(
                q, p, DV, t, n, spec=SPEC, kv_sz=sz),
            one_chip, q, _pool(jnp.int8), SZ, TABLE, LENS)
    else:
        text = _compile(
            lambda q, p, t, n: ops.etap_decode_mla_paged(
                q, p, DV, t, n, spec=SPEC),
            one_chip, q, _pool(), TABLE, LENS)
    assert "tpu_custom_call" in text


def test_paged_splitkv_partial_and_combine(one_chip):
    spec = SPEC.replace(kv_splits=4)
    text = _compile(
        lambda q, p, t, n: ops.etap_decode_mla_paged_splitkv(
            q, p, DV, t, n, spec=spec),
        one_chip, ((B, H, W), jnp.bfloat16), _pool(), TABLE, LENS)
    assert text.count("tpu_custom_call") >= 2     # partial + combine


@pytest.mark.parametrize("chunk", [64, 256])
def test_paged_prefill(one_chip, chunk):
    text = _compile(
        lambda q, p, t, s: ops.etap_prefill_mla_paged(
            q, p, DV, t, s, spec=SPEC),
        one_chip, ((B, chunk, H, W), jnp.bfloat16), _pool(), TABLE, LENS)
    assert "tpu_custom_call" in text


def test_paged_verify(one_chip):
    k = 4
    text = _compile(
        lambda q, p, t, s, qp: ops.etap_verify_mla_paged(
            q, p, DV, t, s, qp, spec=SPEC),
        one_chip, ((B, k, H, W), jnp.bfloat16), _pool(), TABLE, LENS,
        ((B, k), jnp.int32))
    assert "tpu_custom_call" in text


def test_decode_step_full_width(one_chip, chip_smoke):
    cfg = dataclasses.replace(chip_smoke.chip_share_config(),
                              use_kernels=True)
    layout = layout_for(B, NB * PAGE, block_size=PAGE)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: model.init_paged_cache(cfg, layout))

    def step(p, c, tok, table, lengths):
        return model.decode_step(p, cfg, c, tok, None, spec=SPEC,
                                 cache_layout="paged", block_table=table,
                                 lengths=lengths)

    text = _compile(step, one_chip, params, cache, ((B,), jnp.int32),
                    TABLE, LENS)
    assert "tpu_custom_call" in text


# ------------------------------------------------ the pool kept in place
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\{[^}]*\} "
                    r"([\w-]+)\(")
_COMP = re.compile(r"^(?:ENTRY )?%(\S+) .*\{\s*$")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_BODY = re.compile(r"body=%([\w.\-]+)")
_COPYING = ("copy", "dynamic-slice", "dynamic-update-slice")
CELL_TOKENS = 16_384 + 128


def whole_pool_copies(text, n_blocks, row):
    """(name, opcode, shape, in a loop body) of every copy, dynamic-slice
    or dynamic-update-slice in compiled HLO ``text``, or fusion wrapping
    one, whose result is whole layer pools: a shape ending in ``row``
    (page, latent) whose leading dims hold a multiple of ``n_blocks``
    blocks — ``[n, N, page, F]``, ``[N, page, F]`` or ``[n*N, page, F]``.
    Instructions inside fused computations are judged through their
    fusion; the in-place row scatter is no copy and is not listed."""
    comps, body, fused, loops = {}, None, set(), set()
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            body = comps.setdefault(m.group(1), [])
            continue
        loop = _BODY.search(line) if " while(" in line else None
        if loop:
            loops.add(loop.group(1))
        m = _INSTR.match(line)
        if m and body is not None:
            callee = _CALLS.search(line) if m.group(3) == "fusion" else None
            if callee:
                fused.add(callee.group(1))
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            body.append((m.group(1), m.group(3), dims,
                         callee.group(1) if callee else None))
    found = []
    for name, instrs in comps.items():
        if name in fused:
            continue
        for op_name, op, dims, callee in instrs:
            whole = (len(dims) > len(row) and dims[-len(row):] == row
                     and math.prod(dims[:-len(row)]) % n_blocks == 0)
            wraps = op == "fusion" and any(
                o in _COPYING for _, o, _, _ in comps.get(callee, ()))
            if whole and (op in _COPYING or wraps):
                found.append((op_name, op, dims, name in loops))
    return found


@pytest.fixture(scope="module")
def r1_programs(one_chip, chip_smoke):
    """The serve programs of a 4-layer R1 chip share (3 scanned dense
    layers + 1 lone MoE layer) for the described chip, over the
    r1-doc-decode cell's pool (16 slots of 16,512 tokens: 4,129 blocks,
    where a 576-wide row would put the block axis minor in the chip's
    default layout), with shape arguments placed on it."""
    cfg = dataclasses.replace(chip_smoke.chip_share_config(),
                              use_kernels=True)
    layout = layout_for(B, CELL_TOKENS, block_size=PAGE)
    progs = serve.paged_programs(
        cfg, layout, attn_spec.AttnSpec(mode="etap", interpret=False))

    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    def arr(shape):
        return place(jax.ShapeDtypeStruct(shape, jnp.int32))

    params = jax.tree.map(place, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), cfg)))
    cache = jax.tree.map(place, jax.eval_shape(progs.init))
    nb = layout.max_blocks
    args = {"decode": (progs.step, (B,), (B, nb)),
            "prefill": (progs.prefill, (1, 512), (1, nb)),
            "verify": (progs.verify, (B, 4), (B, nb))}
    compiled = {}

    def compile_(name):
        if name not in compiled:
            fn, toks, table = args[name]
            compiled[name] = fn.lower(params, cache, arr(toks), arr(table),
                                      arr(table[:1])).compile()
        return compiled[name]
    return cfg, progs, layout, cache, compile_


@pytest.mark.parametrize("program", ["decode", "prefill", "verify"])
def test_serve_program_copies_the_pool_only_at_its_boundary(r1_programs,
                                                          program):
    """No serve program copies a whole layer pool, in the layer scan or
    at its boundary: the pool is carried whole through the scan, and its
    lane-aligned rows make the chip's default layout the row-major one
    the kernels read, so nothing relays it in or out.  Checked at the
    stored width and at the latent's own, so that a pool that went back
    to 576-wide rows cannot pass."""
    _, _, layout, _, compile_ = r1_programs
    text = compile_(program).as_text()
    assert "tpu_custom_call" in text
    for row in ((PAGE, W), (PAGE, LAT)):
        found = whole_pool_copies(text, layout.num_blocks, row)
        assert not found, f"whole-pool copies at row {row}: {found}"


def test_pool_format_agrees_across_every_writer(r1_programs, one_chip):
    """init, the three serve entries, the COW copy and the swap write all
    compile to one pool format, in and out: the pool one program hands on
    is the pool the next was compiled for, so none relays it between
    programs or compiles again for another layout."""
    _, progs, _, cache, compile_ = r1_programs
    want = jax.jit(progs.init, out_shardings=one_chip).lower().compile() \
        .output_formats
    for name in ("decode", "prefill", "verify"):
        c = compile_(name)
        assert c.input_formats[0][1] == want, name
        assert c.output_formats[1] == want, name
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    rows = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        (s.shape[0], 2) + s.shape[2:], s.dtype, sharding=one_chip), cache)
    for fn, args in ((progs.copy_block, (i32, i32)),
                     (progs.write_blocks, (ids, rows))):
        c = fn.lower(cache, *args).compile()
        assert c.input_formats[0][0] == want
        assert c.output_formats == want


# -------------------------------------------- DeepSeek Sparse Attention
V32_SLOTS, V32_TOKENS = 8, 32_768 + 256          # v32-doc-decode-32k's pool


@pytest.mark.parametrize("rows", [1, 512])
def test_dsa_index_kernel(one_chip, rows):
    """The index-score kernel, as the decode step (one row a sequence) and
    a prefill chunk (512 rows) call it, over the v32 cell's key pool."""
    nb = V32_TOKENS // PAGE
    n_blocks = 1 + V32_SLOTS * nb
    b = V32_SLOTS if rows == 1 else 1
    fn = ops.dsa_index_decode_paged if rows == 1 else \
        ops.dsa_index_prefill_paged
    text = _compile(
        lambda q, w, p, t, n: fn(q, w, p, t, n, spec=SPEC), one_chip,
        ((b, rows, 64, 128), jnp.bfloat16), ((b, rows, 64), jnp.float32),
        ((n_blocks, PAGE, 128), jnp.bfloat16), ((b, nb), jnp.int32),
        ((b,), jnp.int32))
    assert "tpu_custom_call" in text


def test_dsa_sparse_decode_and_masked_prefill(one_chip):
    text = _compile(
        lambda q, r, n: ops.dsa_sparse_decode(q, r, DV, n, spec=SPEC),
        one_chip, ((B, H, W), jnp.bfloat16), ((B, 2048, W), jnp.bfloat16),
        LENS)
    assert "tpu_custom_call" in text
    text = _compile(
        lambda q, p, t, s, sel: ops.etap_prefill_mla_paged(
            q, p, DV, t, s, spec=SPEC, sel=sel),
        one_chip, ((1, 512, H, W), jnp.bfloat16), _pool(), ((1, NB), jnp.int32),
        ((1,), jnp.int32), ((1, 512, NB * PAGE), jnp.bool_))
    assert "tpu_custom_call" in text


def _v32_config():
    """The deepseek-v32-tp8 share (3 dense + 4 MoE layers, indexer whole)
    as the benchmark builds it, on the kernels."""
    import sys
    from pathlib import Path
    bench = Path(__file__).resolve().parents[1] / "bench"
    sys.path.insert(0, str(bench))
    from harness import config
    c = config.load(bench / "configs" / "deepseek-v32-tp8.json")
    return dataclasses.replace(config.model_config(c, "v32"),
                               use_kernels=True)


@pytest.fixture(scope="module")
def v32_programs(one_chip):
    """The decode and prefill programs of the deepseek-v32-tp8 share over
    the v32-doc-decode-32k pool (8 slots of 33,024 tokens: 4,129
    blocks)."""
    cfg = _v32_config()
    layout = layout_for(V32_SLOTS, V32_TOKENS, block_size=PAGE)
    progs = serve.paged_programs(
        cfg, layout, attn_spec.AttnSpec(mode="etap", interpret=False))

    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    def arr(shape):
        return place(jax.ShapeDtypeStruct(shape, jnp.int32))

    params = jax.tree.map(place, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), cfg)))
    cache = jax.tree.map(place, jax.eval_shape(progs.init))
    nb = layout.max_blocks
    texts = {name: fn.lower(params, cache, arr(toks), arr(table),
                            arr(table[:1])).compile().as_text()
             for name, fn, toks, table in (
                 ("decode", progs.step, (V32_SLOTS,), (V32_SLOTS, nb)),
                 ("prefill", progs.prefill, (1, 512), (1, nb)))}
    return cfg, layout, texts


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_dsa_programs_compile_with_r1s_boundary_copies(v32_programs,
                                                       program):
    """The DSA decode step holds the index kernel and the sparse decode and
    no paged ETAP decode over the whole latent pool; the prefill program
    holds its own index kernel and the masked prefill kernel.  As for R1
    (above), no latent pool is copied whole, at either width, and no
    index-key pool either: all of them are row-major by default."""
    _, layout, texts = v32_programs
    text = texts[program]
    names = set(re.findall(r"%(dsa_\w+|etap_\w+)\.\d+ = ", text))
    if program == "decode":
        assert names == {"dsa_index_decode_paged", "dsa_sparse_decode"}
    else:
        assert names == {"dsa_index_prefill_paged", "etap_prefill_mla_paged"}
    for row in ((PAGE, W), (PAGE, LAT), (PAGE, 128)):
        found = whole_pool_copies(text, layout.num_blocks, row)
        assert not found, f"whole-pool copies at row {row}: {found}"


@pytest.mark.parametrize("arch,slots,tokens,blocks", [
    ("r1", B, CELL_TOKENS, 4_129),               # r1-doc-decode
    ("r1", B, 16_384 + 32, 4_113),               # r1-longprompt-prefill
    ("v32", V32_SLOTS, V32_TOKENS, 4_129),       # a 4-layer group, k_idx
    ("r1", B, 32_768 + 512, 8_321),              # the 32K cells
    ("r1", B, 65_536, 16_385),                   # the 64K cell
])
def test_pool_default_layout_is_row_major(one_chip, chip_smoke, arch, slots,
                                          tokens, blocks):
    """The chip's default format of every pool leaf ``progs.init`` returns
    is row-major at the cells' block counts, so the pool a cache-loaded
    executable hands back (in the default format) is the one the kernels
    read, and no program relays it."""
    cfg = _v32_config() if arch == "v32" else dataclasses.replace(
        chip_smoke.chip_share_config(), use_kernels=True)
    layout = layout_for(slots, tokens, block_size=PAGE)
    assert layout.num_blocks == blocks
    progs = serve.paged_programs(
        cfg, layout, attn_spec.AttnSpec(mode="etap", interpret=False))
    out = jax.jit(progs.init, out_shardings=one_chip).lower().compile() \
        .output_formats
    leaves = jax.tree.leaves(out)
    shapes = [a.shape for a in jax.tree.leaves(jax.eval_shape(progs.init))]
    assert len(leaves) == len(shapes)
    assert (4 if arch == "v32" else 3, blocks, PAGE, W) in shapes
    for f, shape in zip(leaves, shapes, strict=True):
        assert f.layout.major_to_minor == tuple(range(len(shape))), shape
