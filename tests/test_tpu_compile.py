"""Compile rehearsals for the TPU v5e: the served kernels at real widths.

Each case lowers and compiles for a *described* v5e chip (no chip is
attached), so Mosaic's tiling and memory rules are checked on every run
at no chip time: the fused paged-attention kernel at ``qwen3-1.7b`` GQA
widths (bf16 and int8 pages), at ``deepseek-v3-671b`` MLA latent widths,
and a 2-layer published-width ``qwen3-1.7b`` decode step on the paged
fused path — on one chip and on a dp=2 x tp=2 serving mesh over the
four chips, where the kernel runs inside a ``shard_map`` (Mosaic kernels
cannot be partitioned automatically) — with the kernel present as a
``tpu_custom_call``. At the one-chip cell's engine shape the compiled
step is also searched for any copy, slice or update-slice of a value
the size of an arena leaf or of one layer's pages: the paged arena must
stay in place, in its own layout, through the whole step.

The topology is described inside a module fixture (never at import):
only the worker that runs this file loads the TPU compiler library.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels import common as kernel_common
from repro.kernels.paged_attention import paged_decode_attention
from repro.models.api import build_model
from repro.parallel import sharding as shard_rules

# Serving shape of the one-chip qwen3-1.7b bring-up: 8 slots x 2048
# positions, chunk 32, 16-token blocks.
SLOTS, CHUNK, MAX_SEQ, BLOCK = 8, 32, 2048, 16
MAX_BLOCKS = MAX_SEQ // BLOCK
NUM_PAGES = SLOTS * MAX_BLOCKS + 1          # + the null page


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile cannot be read back from the persistent
    cache without the chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture(scope="module")
def serving_mesh(topo):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def _slot_vectors(spec):
    return (spec((SLOTS, MAX_BLOCKS), jnp.int32), spec((SLOTS,), jnp.int32),
            spec((SLOTS,), jnp.int32))


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_kernel_compiles_gqa_qwen3_1_7b(one_chip, no_persistent_cache,
                                              kv):
    cfg = get_config("qwen3-1.7b")
    h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim()
    spec = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    page_dt = jnp.bfloat16 if kv == "bf16" else jnp.int8
    pages = spec((1, NUM_PAGES, hkv, BLOCK, d), page_dt)
    q = spec((SLOTS, CHUNK, h, d), jnp.bfloat16)
    if kv == "bf16":
        def fn(q, k, v, t, p, n):
            return paged_decode_attention(q, k, v, t, p, sm_scale=d ** -0.5,
                                          lengths=n, interpret=False)
        args = (q, pages, pages)
    else:
        scales = spec((1, NUM_PAGES, hkv, BLOCK), jnp.float16)

        def fn(q, k, v, ks, vs, t, p, n):
            return paged_decode_attention(q, k, v, t, p, sm_scale=d ** -0.5,
                                          k_scales=ks, v_scales=vs,
                                          lengths=n, interpret=False)
        args = (q, pages, pages, scales, scales)
    compiled = _compile(fn, *args, *_slot_vectors(spec))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_kernel_compiles_mla_deepseek_v3(one_chip, no_persistent_cache,
                                               kv):
    """Absorbed MLA: 128 heads against rank-512 latents (also V) plus the
    64-wide decoupled-RoPE side, one decode query per slot."""
    m = get_config("deepseek-v3-671b")
    h, rank, rope = m.num_heads, m.mla.kv_lora_rank, m.mla.qk_rope_head_dim
    spec = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    page_dt = jnp.bfloat16 if kv == "bf16" else jnp.int8
    q1 = spec((SLOTS, 1, h, rank), jnp.float32)
    q2 = spec((SLOTS, 1, h, rope), jnp.float32)
    ckv = spec((1, NUM_PAGES, 1, BLOCK, rank), page_dt)
    krope = spec((1, NUM_PAGES, 1, BLOCK, rope), page_dt)
    scales = spec((1, NUM_PAGES, 1, BLOCK), jnp.float16)
    sm = (m.mla.qk_nope_head_dim + rope) ** -0.5

    def fn(q1, q2, ckv, krope, cs, ks, t, p, n):
        quant = dict(k_scales=cs, k2_scales=ks) if kv == "int8" else {}
        return paged_decode_attention(q1, ckv, None, t, p, sm_scale=sm,
                                      q2=q2, k2_pages=krope, lengths=n,
                                      out_dtype=jnp.float32,
                                      interpret=False, **quant)
    compiled = _compile(fn, q1, q2, ckv, krope, scales, scales,
                        *_slot_vectors(spec))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("placement", ["one_chip", "dp2_tp2"])
def test_decode_step_compiles_qwen3_1_7b_two_layers(
        request, no_persistent_cache, monkeypatch, placement):
    """A 2-layer, published-width qwen3-1.7b serving pass (the engine's
    model pass: chunked decode step over the paged arena, fused kernel)
    compiles for the chip with the kernel as a Mosaic custom call — the
    backend decision steered to "compiled" here, as a TPU makes it. On
    the mesh, params, arena and slot operands carry the engine's serving
    shardings and the pass is traced under its ``activation_mesh``."""
    assert kernel_common.interpret_kernels()        # this host is a CPU
    monkeypatch.setattr(kernel_common, "interpret_kernels", lambda: False)
    cfg = dataclasses.replace(get_config("qwen3-1.7b"), num_layers=2)
    model = build_model(cfg)
    params = model.abstract_params()
    specs = model.chunked_step_specs(SLOTS, CHUNK, MAX_SEQ,
                                     block_size=BLOCK,
                                     num_blocks=NUM_PAGES - 1)
    mesh = None
    if placement == "one_chip":
        chip = request.getfixturevalue("one_chip")
        param_sh = jax.tree.map(lambda _: chip, params)
        cache_sh = jax.tree.map(lambda _: chip, specs["cache"])
        slot_sh = lambda ndim: chip
    else:
        mesh = request.getfixturevalue("serving_mesh")
        param_sh = shard_rules.serving_param_shardings(params, mesh)
        cache_sh = shard_rules.serving_cache_shardings(
            specs["cache"], mesh,
            (True,) * len(jax.tree.leaves(specs["cache"])))
        slot_sh = lambda ndim: shard_rules.slot_sharding(mesh, ndim)
    place = lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
    slot = lambda x: place(x, slot_sh(x.ndim))

    def step(p, tokens, pos, lengths, cache, tables):
        return model.decode_step(
            p, tokens, pos, cache, lengths=lengths, block_tables=tables,
            paged_impl="fused", interpret=kernel_common.interpret_kernels())
    with shard_rules.activation_mesh(mesh):
        compiled = _compile(
            step, jax.tree.map(place, params, param_sh),
            slot(specs["tokens"]), slot(specs["positions"]),
            slot(specs["lengths"]),
            jax.tree.map(place, specs["cache"], cache_sh),
            slot(specs["block_tables"]))
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem is None or mem.temp_size_in_bytes < 16 * 2 ** 30


# The engine shape of the ``qwen3-1.7b.chat`` cell: 32 slots, chunk 16,
# 256-token blocks, 200 blocks.
CELL_SLOTS, CELL_CHUNK, CELL_MAX_SEQ, CELL_BLOCK, CELL_BLOCKS = \
    32, 16, 4096, 256, 200
_MOVES = {"copy", "dynamic-slice", "dynamic-update-slice"}
_HLO_RESULT = re.compile(
    r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\](?:\{[^}]*\})? "
    r"([\w\-]+)\(")


def _arena_moves(hlo_text, shapes):
    """Lines of a compiled module that copy, slice or update-slice a value
    shaped like one of ``shapes``: plain ops by opcode, fusions by the ops
    the compiler names them after (``copy_bitcast_fusion``,
    ``constant_dynamic-slice_fusion``, ...). Asynchronous moves between
    memory spaces (``copy-start``/``copy-done``) are not among them."""
    found = []
    for line in hlo_text.splitlines():
        m = _HLO_RESULT.match(line)
        if m is None:
            continue
        name, dims, op = m.groups()
        dims = tuple(int(d) for d in dims.split(",") if d)
        kinds = set(name.split(".")[0].split("_")) if op == "fusion" \
            else {op}
        if dims in shapes and kinds & _MOVES:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_decode_step_keeps_arena_in_place(one_chip, no_persistent_cache,
                                          monkeypatch, kv):
    """The published-width qwen3-1.7b step (2 layers) at the cell's
    engine shape, arena donated as the engine donates it: no op copies,
    slices or update-slices a whole arena leaf or one layer's page array,
    and the step's temporaries stay below one layer's page array. A layer
    scan that takes the arena as ``xs`` and rebuilds it as ``ys``, or an
    insert that scatters in a layout other than the arena's, fails both."""
    monkeypatch.setattr(kernel_common, "interpret_kernels", lambda: False)
    cfg = dataclasses.replace(get_config("qwen3-1.7b"), num_layers=2)
    model = build_model(cfg)
    kw = {} if kv == "bf16" else {"kv_quant": "int8"}
    specs = model.chunked_step_specs(CELL_SLOTS, CELL_CHUNK, CELL_MAX_SEQ,
                                     block_size=CELL_BLOCK,
                                     num_blocks=CELL_BLOCKS, **kw)
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)

    def step(p, tokens, pos, lengths, cache, tables):
        return model.decode_step(
            p, tokens, pos, cache, lengths=lengths, block_tables=tables,
            paged_impl="fused", interpret=kernel_common.interpret_kernels())
    compiled = jax.jit(step, donate_argnums=(4,)).lower(
        jax.tree.map(place, model.abstract_params()),
        place(specs["tokens"]), place(specs["positions"]),
        place(specs["lengths"]), jax.tree.map(place, specs["cache"]),
        place(specs["block_tables"])).compile()

    leaves = jax.tree.leaves(specs["cache"])
    shapes = {tuple(x.shape) for x in leaves} | \
        {tuple(x.shape[1:]) for x in leaves}
    assert _arena_moves(compiled.as_text(), shapes) == []
    layer_pages = max(np.prod(x.shape[1:]) * x.dtype.itemsize
                      for x in leaves)
    mem = compiled.memory_analysis()
    assert mem is not None
    assert mem.temp_size_in_bytes < layer_pages
