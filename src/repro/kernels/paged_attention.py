"""Fused paged-attention decode kernel: block-table-native KV reads.

The serving engine's paged decode path used to gather every slot's
physical pages into a dense ``(B, max_blocks*block_size, ...)`` logical
view (``attention.paged_view``) before attending — per-step KV traffic
scaling with *arena capacity* instead of live tokens, exactly the
data-movement class the paper's system evaluation names as the LLM
serving bottleneck. This kernel removes the materialization: the grid
walks each slot's **block table**, and the K/V ``BlockSpec`` index maps
resolve ``(slot, kv_block)`` to a physical page id through a
scalar-prefetched table (the PagedAttention design, on the blocked
online-softmax skeleton of ``kernels/flash_attention.py``).

Layout contract (see ``PagedKVArena.page_layout``):

  q        (B, C, H, D)        — C >= 1 chunked-decode queries per slot
  k_pages  (L, NP, Hkv, bs, D) — the arena's stacked head-major physical
  v_pages  (L, NP, Hkv, bs, Dv)  pages of every layer incl. the trailing
                                 null page; H % Hkv == 0 (GQA groups).
                                 One kernel block is one (layer, page,
                                 kv-head) tile (bs, D): its last two dims
                                 are the array's own, which is what
                                 Mosaic's (8, 128) tiling rule needs
  layer    scalar int32        — the layer whose pages are read; the
                                 stacked arena is read in place, never
                                 sliced per layer
  tables   (B, MB) int32       — logical block -> physical page; entries
                                 past a slot's allocation hold the null
                                 page id (finite garbage, always masked)
  positions (B,) int32         — base position of each slot's chunk;
                                 query i sits at base + i and attends
                                 kv positions <= base + i (causal depth)

MLA runs the same kernel in its absorbed-matmul form: q is the
rank-projected ``q_eff`` against the compressed ``ckv`` pages (which are
also V), and the decoupled RoPE side joins the scores through the
optional ``q2``/``k2_pages`` operands — so the compressed cache is
attended in place, never expanded *and* never gathered.

Quantized pages (``k_scales`` passed): K/V pages hold int8 codes and a
parallel *scale page* per pages operand carries one float16 scale per
(kv-head, in-page position) — ``(L, NP, Hkv, bs)`` next to ``(L, NP,
Hkv, bs, D)`` — quantized over the feature axis at insert time (see
``PagedKVArena.page_layout``). A scale block is the whole ``(Hkv, bs)``
page (full dims, so it tiles); the kernel picks its kv-head's row and
dequantizes in VMEM inside the block walk (``k = int8 * scale`` in
f32, same for V and the MLA RoPE side), so the per-block DMA moves
~(D+2)/(2D) of the bf16 bytes and the arithmetic is unchanged f32
online softmax. A zeroed page
dequantizes to exactly zero (code 0 x scale 0), so the arena's
rollback/CoW/null-page contracts carry over bit-for-bit.

Grid: ``(B, Hkv, MB)`` with f32 running max/sum statistics carried in
VMEM scratch across the kv-block axis. Blocks past a slot's live depth
(``base + C - 1``) are skipped two ways: the index map clamps to the
last live block (Pallas elides the re-fetch of an unchanged block — no
DMA) and ``pl.when`` skips the compute. Per-step KV bytes are therefore
O(live tokens), not O(arena).

``interpret=True`` runs the same kernel body through the Pallas
interpreter so CPU CI exercises the exact serving code path; callers
take the flag from ``kernels.common.interpret_kernels`` (compiled on a
TPU, interpreted only on the CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import MASK_VALUE


def _kernel(tables_ref, pos_ref, len_ref, layer_ref, *refs, sm_scale,
            block_size, group, has_rope, shared_kv, quantized):
    """One (slot, kv-head, kv-block) step of the online softmax."""
    del layer_ref                       # read by the index maps only
    refs = list(refs)
    q1_ref = refs.pop(0)
    q2_ref = refs.pop(0) if has_rope else None
    k1_ref = refs.pop(0)
    k1s_ref = refs.pop(0) if quantized else None
    k2_ref = refs.pop(0) if has_rope else None
    k2s_ref = refs.pop(0) if has_rope and quantized else None
    # MLA's compressed latents are both K and V: sharing the ref means
    # one DMA per live block, not two.
    v_ref = k1_ref if shared_kv else refs.pop(0)
    vs_ref = k1s_ref if shared_kv else (refs.pop(0) if quantized else None)
    o_ref = refs.pop(0)
    acc_ref, m_ref, l_ref = refs[-3:]

    def page(ref, s_ref):
        """(bs, D) f32 page tile, dequantized when the arena is int8:
        code * per-(kv-head, position) scale — a zeroed page (code 0,
        scale 0) dequantizes to exactly 0, preserving the rollback
        bit-identity contract on the quantized layout."""
        x = ref[0, 0, 0].astype(jnp.float32)
        if s_ref is not None:
            x = x * _scale_column(_f16_bits_to_f32(s_ref[0, 0]), hkv_idx)
        return x
    b = pl.program_id(0)
    hkv_idx = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    pos0 = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Last block any of this slot's *valid* queries can see (query i
    # attends positions <= pos0 + i, and only the first len_ref[b]
    # queries are real — the tail is garbage-by-contract the engine
    # never reads). Blocks past it carry no live tokens.
    last_live = (pos0 + jnp.maximum(len_ref[b], 1) - 1) // block_size

    @pl.when(j <= last_live)
    def _body():
        q = q1_ref[0, 0].astype(jnp.float32)              # (CG, D)
        k = page(k1_ref, k1s_ref)                         # (bs, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (CG, bs)
        if has_rope:                                      # MLA rope scores
            s = s + jax.lax.dot_general(
                q2_ref[0, 0].astype(jnp.float32),
                page(k2_ref, k2s_ref),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        s = s * sm_scale
        # Row r is query r // group; its causal depth is pos0 + r//group.
        ki = j * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qc = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        s = jnp.where(ki <= pos0 + qc, s, MASK_VALUE)
        m_prev = m_ref[...]                               # (CG, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = k if shared_kv else page(v_ref, vs_ref)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _f16_bits_to_f32(bits):
    """Exact float16 -> float32 from int16 bit patterns. Mosaic cannot
    load float16 on every TPU generation (v5e refuses the vector type),
    so scale pages enter the kernel bitcast to int16 and are widened
    here with integer ops: normals rebias the exponent, subnormals are
    ``mantissa * 2**-24``, and inf/NaN keep an all-ones exponent."""
    b = bits.astype(jnp.int32) & 0xFFFF
    sign = b >> 15
    exp = (b >> 10) & 0x1F
    man = b & 0x3FF
    exp32 = jnp.where(exp == 0x1F, 0xFF, exp + (127 - 15))
    normal = jax.lax.bitcast_convert_type(
        (sign << 31) | (exp32 << 23) | (man << 13), jnp.float32)
    sub = man.astype(jnp.float32) * (2.0 ** -24)
    sub = jnp.where(sign == 1, -sub, sub)
    return jnp.where(exp == 0, sub, normal)


def _scale_column(scales, h):
    """Row ``h`` of an ``(Hkv, bs)`` scale tile as a ``(bs, 1)`` column.

    Masked sums select the row and turn it into a column without a
    dynamic sublane slice or a transpose of a narrow tile, which Mosaic
    may refuse; each sum adds exact zeros, so the scale is unchanged."""
    rows = jax.lax.broadcasted_iota(jnp.int32, scales.shape, 0)
    row = jnp.sum(jnp.where(rows == h, scales, 0.0), axis=0, keepdims=True)
    n = scales.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _fold_heads(x, b, c, hkv, group):
    """(B, C, H, D) -> (B, Hkv, C*G, D): row r of a kv-head's query tile
    is (chunk entry r // G, group member r % G) — head h = hkv*G + g,
    matching ``decode_attention``'s grouped-query layout."""
    d = x.shape[-1]
    x = x.reshape(b, c, hkv, group, d)
    return jnp.transpose(x, (0, 2, 1, 3, 4)).reshape(b, hkv, c * group, d)


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "out_dtype", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, positions, *,
                           sm_scale: float, layer=0, q2=None, k2_pages=None,
                           k_scales=None, v_scales=None, k2_scales=None,
                           lengths=None, out_dtype=None,
                           interpret: bool = False):
    """Fused paged decode attention over a chunk of C queries per slot.

    q: (B, C, H, D); k_pages/v_pages: (L, NP, Hkv, bs, D/Dv) the
    arena's stacked head-major physical pages (NP includes the trailing
    null page); ``layer``: scalar int32, the layer attended — it rides
    as a scalar-prefetch operand into the page index maps, so the
    stacked leaf is read in place (a caller holding one layer's pages
    passes ``pages[None]`` and layer 0); block_tables: (B, MB) int32;
    positions: (B,) int32 chunk base positions. ``v_pages=None``
    shares the K pages as V (MLA's compressed latents are both — one
    DMA per live block instead of two). Optional q2 (B, C, H, D2) /
    k2_pages (L, NP, Hkv, bs, D2) contribute a second score term before
    the softmax (MLA's decoupled-RoPE side). ``lengths`` (B,) int32:
    valid queries per row (chunked prefill) — each row's block walk
    stops at its last *valid* query's causal depth, so a steady-state
    decode row (lengths == 1) never over-fetches for its garbage tail.

    Quantized arenas pass int8 pages plus ``k_scales``/``v_scales``/
    ``k2_scales`` — (L, NP, Hkv, bs) per-(kv-head, position) scale pages
    riding the same block-table index map — and the kernel dequantizes
    inside the block walk (all scale operands must accompany their
    pages; ``v_scales`` is omitted exactly when ``v_pages`` is).
    Returns (B, C, H, Dv) in ``out_dtype`` (default q.dtype).
    """
    b, c, h, d = q.shape
    _, _, hkv, bs, _ = k_pages.shape
    shared_kv = v_pages is None
    quantized = k_scales is not None
    if quantized:
        assert (v_scales is None) == shared_kv, \
            "v_scales must accompany v_pages"
        assert (k2_scales is None) == (k2_pages is None), \
            "k2_scales must accompany k2_pages"
    dv = k_pages.shape[-1] if shared_kv else v_pages.shape[-1]
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    cg = c * group
    nkb = block_tables.shape[1]
    has_rope = q2 is not None
    positions = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (b,))
    if lengths is None:
        lengths = jnp.full((b,), c, jnp.int32)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    def live_page(bb, jj, tables, pos, lens):
        # Clamp dead blocks to the last live one: Pallas skips the
        # re-fetch of an unchanged block index, so trailing table entries
        # cost no DMA (the compute is skipped by pl.when).
        last = (pos[bb] + jnp.maximum(lens[bb], 1) - 1) // bs
        return tables[bb, jnp.minimum(jj, last)]

    def page_index(bb, hh, jj, tables, pos, lens, lyr):
        return (lyr[0], live_page(bb, jj, tables, pos, lens), hh, 0, 0)

    def q_index(bb, hh, jj, tables, pos, lens, lyr):
        return (bb, hh, 0, 0)

    def scale_index(bb, hh, jj, tables, pos, lens, lyr):
        # Scale pages (L, NP, Hkv, bs) ride the same clamped table walk
        # as their int8 pages — one extra (Hkv, bs) fetch per live block.
        return (lyr[0], live_page(bb, jj, tables, pos, lens), 0, 0)

    page_spec = lambda dim: pl.BlockSpec((1, 1, 1, bs, dim), page_index)
    scale_spec = pl.BlockSpec((1, 1, hkv, bs), scale_index)
    # float16 scale pages travel as their int16 bits (see _f16_bits_to_f32).
    bits = lambda s: jax.lax.bitcast_convert_type(
        jnp.asarray(s, jnp.float16), jnp.int16)
    in_specs = [pl.BlockSpec((1, 1, cg, d), q_index)]
    args = [_fold_heads(q, b, c, hkv, group)]
    if has_rope:
        d2 = q2.shape[-1]
        in_specs.append(pl.BlockSpec((1, 1, cg, d2), q_index))
        args.append(_fold_heads(q2, b, c, hkv, group))
    in_specs.append(page_spec(d))
    args.append(k_pages)
    if quantized:
        in_specs.append(scale_spec)
        args.append(bits(k_scales))
    if has_rope:
        in_specs.append(page_spec(k2_pages.shape[-1]))
        args.append(k2_pages)
        if quantized:
            in_specs.append(scale_spec)
            args.append(bits(k2_scales))
    if not shared_kv:
        in_specs.append(page_spec(dv))
        args.append(v_pages)
        if quantized:
            in_specs.append(scale_spec)
            args.append(bits(v_scales))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, hkv, nkb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, cg, dv), q_index),
        scratch_shapes=[
            pltpu.VMEM((cg, dv), jnp.float32),
            pltpu.VMEM((cg, 1), jnp.float32),
            pltpu.VMEM((cg, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, block_size=bs,
                          group=group, has_rope=has_rope,
                          shared_kv=shared_kv, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, cg, dv),
                                       out_dtype or q.dtype),
        name="paged_decode_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), positions, lengths, layer, *args)
    out = out.reshape(b, hkv, c, group, dv)
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(b, c, h, dv)
