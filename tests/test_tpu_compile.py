"""Compile the serve path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel entry (or the whole jitted paged
decode step) at DeepSeek-R1 chip-share widths — B 16, H 16, latent 576,
Dv 512, page 64, bf16 — for one chip of a ``v5e:2x2`` topology, and
asserts the compiled program holds a Mosaic kernel (``tpu_custom_call``).
Mosaic's block-tiling rule and its VMEM budget are enforced here, where
interpret mode enforces neither.

The topology is described inside a module fixture (never at import):
only one process may hold the TPU library, and xdist workers all import
this file.  The persistent compilation cache is off around these
compiles — an entry written for a described chip cannot be read back.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import attn_spec
from repro.kernels.etap import ops
from repro.models import model
from repro.runtime.paged_cache import layout_for

B, H, LAT, DV, PAGE, NB = 16, 16, 576, 512, 64, 64
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
    return (N_BLOCKS, PAGE, LAT), dtype


TABLE = ((B, NB), jnp.int32)
LENS = ((B,), jnp.int32)
SZ = ((N_BLOCKS, PAGE, 2), jnp.float32)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_mla_decode(one_chip, kv):
    q = ((B, H, LAT), jnp.bfloat16)
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
        one_chip, ((B, H, LAT), jnp.bfloat16), _pool(), TABLE, LENS)
    assert text.count("tpu_custom_call") >= 2     # partial + combine


@pytest.mark.parametrize("chunk", [64, 256])
def test_paged_prefill(one_chip, chunk):
    text = _compile(
        lambda q, p, t, s: ops.etap_prefill_mla_paged(
            q, p, DV, t, s, spec=SPEC),
        one_chip, ((B, chunk, H, LAT), jnp.bfloat16), _pool(), TABLE, LENS)
    assert "tpu_custom_call" in text


def test_paged_verify(one_chip):
    k = 4
    text = _compile(
        lambda q, p, t, s, qp: ops.etap_verify_mla_paged(
            q, p, DV, t, s, qp, spec=SPEC),
        one_chip, ((B, k, H, LAT), jnp.bfloat16), _pool(), TABLE, LENS,
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
