"""Attention mixers: GQA (w/ qk-norm, bias, M-RoPE) and DeepSeek MLA.

Prefill/train paths use memory-efficient chunked attention (pure-jnp online
softmax — the XLA-lowered twin of the Pallas flash kernel, required for 32k
sequences); decode paths attend a *chunk* of C consecutive queries against
the KV cache (C == 1 is the classic one-token step; C > 1 is the unified
chunked-prefill step, with in-chunk causality as a per-query kv_len mask).

Decode steps take either a *scalar* position (lockstep batch: one
``dynamic_update_slice`` per cache) or a *(B,)* base-position vector (the
continuous-batching serving engine, where every KV-arena slot sits at its
own depth — chunk entry i lands at base + i). With ``lengths`` (B,), rows
write only their first ``lengths[b]`` chunk entries; the invalid tail is
routed out of range and dropped by the scatter, never garbage-written.

KV caches:
  GQA:  {"k": (B, S, Hkv, D), "v": (B, S, Hkv, D)}
  MLA:  {"ckv": (B, S, kv_lora_rank), "krope": (B, S, rope_dim)}  (compressed;
        decode uses the absorbed-matmul form so the cache is never expanded)

Paged decode (``block_tables`` passed): cache leaves are the arena's
stacked head-major physical pages of every layer — GQA {"k": (L, NB,
Hkv, bs, D), ...}, MLA {"ckv": (L, NB, 1, bs, rank), ...} (the latents
carry a unit head axis, so every paged leaf has its position axis at
3) — and ``layer`` picks the layer this call reads and writes, so the
leaf stays in place through the step: never sliced, never copied.
``block_tables`` (B, max_blocks) int32 maps each slot's logical blocks
to pages. The new tokens are scattered to their pages as
``(layer, page, head, offset)`` rows, then attention reads K/V through
the table one of two ways (``paged_impl``):

* ``"fused"`` (default): the Pallas blocked online-softmax kernel in
  ``kernels/paged_attention.py`` resolves (slot, kv_block) -> page via
  scalar-prefetched tables and attends the pages *in place* — per-step
  KV traffic is O(live tokens).
* ``"ref"``: the original jnp gather (``paged_view``) materializes each
  slot's dense (B, max_blocks*block_size, ...) logical view every step —
  O(arena) traffic, kept as the fallback and the differential oracle the
  fused kernel is tested against.

Quantized paged cache (arena built with ``kv_quant="int8"``): each paged
leaf is a dict ``{"q": int8 code pages, "s": float16 scale pages}``
(scale pages (L, NB, Hkv, bs): one scale per (kv-head, position),
quantized over the feature axis at insert time — see ``quantize_kv``). The decode paths detect the dict
structurally, quantize on insert, and either hand code+scale pages to
the fused kernel (which dequantizes in the block walk) or dequantize the
dense gathered view on the ref path. Contiguous caches are never
quantized (the serving engine gates ``kv_quant`` on the paged arena).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import paged_decode_attention
from repro.models import flags, layers
from repro.models.layers import Params
from repro.parallel import sharding as shard_rules

NEG_INF = -1e30


# ----------------------------------------------------------------------
# Memory-efficient chunked attention (online softmax over KV blocks)
# ----------------------------------------------------------------------
def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool, sm_scale: float,
                      q_offset=0, kv_chunk: int = 1024) -> jnp.ndarray:
    """q: (B, Sq, H, D); k, v: (B, Skv, Hkv, D). Returns (B, Sq, H, D).

    ``q_offset``: absolute position of q[0] in the KV timeline (int or
    traced scalar) — decode passes its current position here.
    """
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]                                     # MLA: dv != d
    group = h // hkv
    kv_chunk = min(kv_chunk, skv)
    nchunk = -(-skv // kv_chunk)
    kv_pad = nchunk * kv_chunk
    if kv_pad != skv:
        k = jnp.pad(k, [(0, 0), (0, kv_pad - skv), (0, 0), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, kv_pad - skv), (0, 0), (0, 0)])
    mixed = flags.mixed_intermediates()
    lowp = jnp.bfloat16 if mixed else jnp.float32
    kc = jnp.moveaxis(k.reshape(b, nchunk, kv_chunk, hkv, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nchunk, kv_chunk, hkv, dv), 1, 0)
    qg = q.astype(lowp).reshape(b, sq, hkv, group, d)

    def body(carry, inputs):
        m_prev, l_prev, acc = carry                      # (b,hkv,g,sq[,d])
        idx, kb, vb = inputs
        kb = kb.astype(lowp)                             # (b, c, hkv, d)
        vb = vb.astype(lowp)
        s = jnp.einsum("bqhgd,bchd->bhgqc", qg, kb,
                       preferred_element_type=jnp.float32) * sm_scale
        ki = idx * kv_chunk + jnp.arange(kv_chunk)       # (c,)
        qi = q_offset + jnp.arange(sq)                   # (sq,)
        valid = ki[None, :] < skv
        if causal:
            valid = valid & (ki[None, :] <= qi[:, None])
        else:
            valid = jnp.broadcast_to(valid, (sq, kv_chunk))
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqc,bchd->bhgqd", p.astype(lowp), vb,
                        preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + pv
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hkv, group, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, group, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (jnp.arange(nchunk), kc, vc),
                                  unroll=flags.inner_unroll())
    l = jnp.where(l == 0, 1.0, l)
    out = (acc / l[..., None]).astype(q.dtype)           # (b,hkv,g,sq,dv)
    out = jnp.transpose(out, (0, 3, 1, 2, 4))            # (b,sq,hkv,g,dv)
    return out.reshape(b, sq, h, dv)


def position_vector(position, batch: int) -> jnp.ndarray:
    """Normalize a decode position (scalar or (B,)) to a (B, 1) int array."""
    p = jnp.asarray(position)
    if p.ndim == 0:
        return jnp.broadcast_to(p, (batch, 1))
    return p.reshape(batch, 1)


def query_lengths(kv_len, batch: int, width: int) -> Optional[jnp.ndarray]:
    """Normalize a valid-KV-length spec — None, scalar, (B,) per-slot, or
    (B, C) per-query — to a (B, C) int matrix (or None = no masking)."""
    if kv_len is None:
        return None
    kv_len = jnp.asarray(kv_len)
    if kv_len.ndim == 0:
        return jnp.broadcast_to(kv_len, (batch, width))
    if kv_len.ndim == 1:
        return jnp.broadcast_to(kv_len[:, None], (batch, width))
    return kv_len


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                     sm_scale: float, kv_len=None) -> jnp.ndarray:
    """Decode-side attention: q (B, C, H, D) vs cache k/v (B, S, Hkv, D).
    C == 1 is the classic one-token step; C > 1 is a *chunk* of C
    consecutive queries (unified chunked-prefill step). ``kv_len``:
    scalar, (B,) or (B, C) valid length per query — a chunk passes the
    per-query causal depth ``pos0 + i + 1`` so in-chunk causality is a
    mask, never a shape change.

    With ``flags.mixed_intermediates()`` the KV cache is contracted in its
    stored bf16 dtype (f32 accumulation via preferred_element_type) — no
    f32 copy of the cache is ever materialized, halving decode's dominant
    HBM traffic."""
    b, c, h, d = q.shape
    _, s, hkv, _ = k.shape
    group = h // hkv
    if flags.mixed_intermediates():
        qg = q.astype(k.dtype).reshape(b, c, hkv, group, d)
        sc = jnp.einsum("bqhgd,bshd->bhgqs", qg, k,
                        preferred_element_type=jnp.float32) * sm_scale
    else:
        qg = q.astype(jnp.float32).reshape(b, c, hkv, group, d)
        sc = jnp.einsum("bqhgd,bshd->bhgqs", qg,
                        k.astype(jnp.float32)) * sm_scale
    lens = query_lengths(kv_len, b, c)
    if lens is not None:                        # (B, C) -> (B,1,1,C,1)
        mask = jnp.arange(s)[None, None, None, None, :] \
            < lens[:, None, None, :, None]
        sc = jnp.where(mask, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    if flags.mixed_intermediates():
        o = jnp.einsum("bhgqs,bshd->bqhgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
    else:
        o = jnp.einsum("bhgqs,bshd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, c, h, d).astype(q.dtype)


# ----------------------------------------------------------------------
# GQA attention module (llama/qwen families)
# ----------------------------------------------------------------------
def gqa_init(key, cfg: ModelConfig, fmt: str = "none") -> Params:
    d = cfg.d_model
    hd = cfg.resolved_head_dim()
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "q": layers.linear_init(kq, d, cfg.num_heads * hd, fmt,
                                bias=cfg.qkv_bias),
        "k": layers.linear_init(kk, d, cfg.num_kv_heads * hd, fmt,
                                bias=cfg.qkv_bias),
        "v": layers.linear_init(kv, d, cfg.num_kv_heads * hd, fmt,
                                bias=cfg.qkv_bias),
        "o": layers.linear_init(ko, cfg.num_heads * hd, d, fmt),
    }
    if cfg.qk_norm:
        p["q_norm"] = layers.rmsnorm_init(hd)
        p["k_norm"] = layers.rmsnorm_init(hd)
    return p


def _project_qkv(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                 positions, fmt: str, impl: str, interpret: bool,
                 mrope_positions=None):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim()
    q = layers.linear_apply(p["q"], x, fmt, impl=impl, interpret=interpret)
    k = layers.linear_apply(p["k"], x, fmt, impl=impl, interpret=interpret)
    v = layers.linear_apply(p["v"], x, fmt, impl=impl, interpret=interpret)
    q = q.reshape(b, s, cfg.num_heads, hd)
    k = k.reshape(b, s, cfg.num_kv_heads, hd)
    v = v.reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = layers.rmsnorm_apply(p["q_norm"], q, cfg.norm_eps)
        k = layers.rmsnorm_apply(p["k_norm"], k, cfg.norm_eps)
    if cfg.rope_theta > 0:
        if cfg.mrope and mrope_positions is not None:
            q = layers.apply_mrope(q, mrope_positions, cfg.rope_theta,
                                   cfg.mrope_sections)
            k = layers.apply_mrope(k, mrope_positions, cfg.rope_theta,
                                   cfg.mrope_sections)
        else:
            q = layers.apply_rope(q, positions, cfg.rope_theta)
            k = layers.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_apply(p: Params, cfg: ModelConfig, x: jnp.ndarray,
              positions: jnp.ndarray, *, fmt: str = "none",
              impl: str = "ref", interpret: bool = True,
              causal: bool = True, kv_chunk: int = 1024,
              mrope_positions=None,
              cross_kv: Optional[Tuple] = None) -> jnp.ndarray:
    """Full-sequence (train/prefill) GQA. ``cross_kv``: (k, v) overrides for
    encoder-decoder cross attention (whisper)."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim()
    q, k, v = _project_qkv(p, cfg, x, positions, fmt, impl, interpret,
                           mrope_positions)
    if cross_kv is not None:
        k, v = cross_kv
        causal = False
    o = chunked_attention(q, k, v, causal=causal, sm_scale=hd ** -0.5,
                          kv_chunk=kv_chunk)
    o = o.reshape(b, s, cfg.num_heads * hd)
    return layers.linear_apply(p["o"], o, fmt, impl=impl, interpret=interpret)


def gqa_prefill(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                positions: jnp.ndarray, *, fmt: str = "none",
                impl: str = "ref", interpret: bool = True,
                kv_chunk: int = 1024, mrope_positions=None):
    """Prefill returning (out, kv_cache_entry)."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim()
    q, k, v = _project_qkv(p, cfg, x, positions, fmt, impl, interpret,
                           mrope_positions)
    o = chunked_attention(q, k, v, causal=True, sm_scale=hd ** -0.5,
                          kv_chunk=kv_chunk)
    o = o.reshape(b, s, cfg.num_heads * hd)
    out = layers.linear_apply(p["o"], o, fmt, impl=impl, interpret=interpret)
    return out, {"k": k, "v": v}


def _insert_kv(cache_arr: jnp.ndarray, new: jnp.ndarray,
               position, lengths=None) -> jnp.ndarray:
    """Write (B, C, ...) ``new`` into (B, S, ...) cache.

    C == 1, ``lengths`` None: classic one-token insert at ``position`` —
    a scalar (lockstep batch) or a (B,) vector (per-slot arena depths).

    Chunk insert (``lengths`` given, or C > 1): ``position`` is the (B,)
    base index; row b writes its first ``lengths[b]`` chunk entries at
    ``position[b] + i``. Invalid tail entries are routed out of range and
    *dropped* by the scatter — no garbage ever lands in the cache (unlike
    bucket padding, which relied on rewrite-before-use)."""
    p = jnp.asarray(position)
    new = new.astype(cache_arr.dtype)
    b, c = new.shape[:2]
    if lengths is None and c == 1:
        if p.ndim == 0:
            start = (0, p) + (0,) * (cache_arr.ndim - 2)
            return jax.lax.dynamic_update_slice(cache_arr, new, start)

        def one(cc, n, pi):                              # cc: (S, ...)
            return jax.lax.dynamic_update_slice(
                cc, n, (pi,) + (0,) * (cc.ndim - 1))
        return jax.vmap(one)(cache_arr, new, p)
    s = cache_arr.shape[1]
    idx = jnp.broadcast_to(p, (b,))[:, None] + jnp.arange(c)
    if lengths is not None:
        valid = jnp.arange(c)[None, :] < lengths[:, None]
        idx = jnp.where(valid, idx, s)                   # OOB -> dropped
    return cache_arr.at[jnp.arange(b)[:, None], idx].set(new, mode="drop")


# ----------------------------------------------------------------------
# Quantized KV pages (blocked int8 + per-(position, kv-head) scales)
# ----------------------------------------------------------------------
# Scale storage dtype. float16 (not f32) is load-bearing for the byte
# accounting: the quantized KV stream is (D + 2)/(2D) of bf16 per
# stored feature row, which clears the bench gate even at the reduced
# head_dim of 32 (0.531x); f32 scales would not (0.563x).
KV_QUANT_SCALE_DTYPE = jnp.float16


def quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 quantization over the trailing feature axis:
    ``x`` (..., D) -> (codes int8 (..., D), scales float16 (...,)).
    Scale is amax/127 per feature row (one row per (token, kv-head) for
    GQA K/V; per token for MLA latents) — the same absmax scheme as the
    q8_0 weight format, at insert-time granularity so every cache
    position quantizes independently (rollback can zero single
    positions without touching a shared block scale). An all-zero row
    maps to (codes 0, scale 0), which dequantizes to exactly zero —
    never-written, rolled-back and null pages stay bit-identical."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    q = jnp.round(xf / jnp.where(scale > 0, scale, 1.0)[..., None])
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale.astype(KV_QUANT_SCALE_DTYPE)


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``quantize_kv``: f32 ``codes * scale`` — the same
    arithmetic the fused kernel performs in VMEM during the block walk,
    exposed for the ref (dense-gather) path and the differential tests."""
    return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]


def _paged_insert_quant(leaf: Dict, new: jnp.ndarray, position,
                        block_tables, lengths, layer=0) -> Dict:
    """Quantize-on-insert into a quantized paged leaf ``{"q": int8
    pages, "s": scale pages}`` (see ``PagedKVArena.page_layout``). Codes
    and scales scatter through the same table walk, so the write-drop /
    null-block routing contracts apply to both identically."""
    q, s = quantize_kv(new)
    return {"q": paged_insert_token(leaf["q"], q, position, block_tables,
                                    lengths, layer),
            "s": paged_insert_token(leaf["s"], s, position, block_tables,
                                    lengths, layer)}


def _paged_view_dequant(leaf: Dict, block_tables, layer=0) -> jnp.ndarray:
    """Dense-gather oracle over a quantized paged leaf: gather codes and
    scales through the table, dequantize to the f32 logical view."""
    return dequantize_kv(paged_view(leaf["q"], block_tables, layer),
                         paged_view(leaf["s"], block_tables, layer))


# ----------------------------------------------------------------------
# Paged cache plumbing (block-table gather/scatter inside the jitted step)
# ----------------------------------------------------------------------
def paged_insert_token(pages: jnp.ndarray, new: jnp.ndarray, position,
                       block_tables: jnp.ndarray, lengths=None,
                       layer=0) -> jnp.ndarray:
    """Scatter (B, C, H, ...) ``new`` into layer ``layer`` of the stacked
    head-major (L, NB, H, bs, ...) ``pages`` routed through
    ``block_tables`` (B, max_blocks). C == 1 with ``lengths`` None is the
    classic one-token write at ``position``; the chunk form writes row
    b's first ``lengths[b]`` entries at ``position[b] + i``.

    The write is tiled: the leaf is viewed (a bitcast) as (L, NB, H,
    bs/T, T, ...) and each slot writes the whole T-position tiles its
    valid entries fall in, per head, at ``(layer, page, head, tile)``:
    each tile is its current contents with that slot's entries put in.
    The index arrays are broadcast over (B, tiles, H), so no axis is
    sliced and the window is whole trailing dims: the scatter needs no
    layout but the arena's own, and the donated leaf is written in
    place. ``_tile_rows`` picks T.

    Blocks are uniquely owned by one slot, so active slots never collide
    (the tiles a slot writes lie on its own pages; a tile written twice
    by one slot carries the same values). Single-token writes from
    inactive slots land in the arena's null block (their table entries
    all point there); entries past a row's valid length keep the page's
    current values, and a row with none writes nothing."""
    num_pages, h, bs = pages.shape[1:4]
    feat = pages.shape[4:]                  # (D,), or () on scale pages
    b, c = new.shape[:2]
    n = jnp.full((b,), c, jnp.int32) if lengths is None else lengths
    t = _tile_rows(pages)
    nt = -(-c // t) + 1                     # tiles a chunk can touch
    pos0 = jnp.broadcast_to(jnp.asarray(position, jnp.int32), (b,))
    last = (pos0 + jnp.maximum(n, 1) - 1) // t
    tile = jnp.minimum(pos0[:, None] // t + jnp.arange(nt), last[:, None])
    start = tile * t                                     # (B, nt)
    phys = jnp.take_along_axis(block_tables, start // bs, axis=1)
    phys = jnp.where(n[:, None] > 0, phys, num_pages)    # OOB -> dropped
    # The chunk entry each tile position takes, where one does.
    src = start[:, :, None] + jnp.arange(t) - pos0[:, None, None]
    put = (src >= 0) & (src < n[:, None, None])          # (B, nt, T)
    src = jnp.clip(src, 0, c - 1).reshape(b, nt * t)
    src = src.reshape(src.shape + (1,) * (new.ndim - 2))
    vals = jnp.take_along_axis(new.astype(pages.dtype), src, axis=1)
    vals = jnp.moveaxis(vals.reshape((b, nt, t, h) + feat), 3, 2)
    put = put[:, :, None].reshape((b, nt, 1, t) + (1,) * len(feat))
    tiles = pages.reshape(pages.shape[:3] + (bs // t, t) + feat)
    rows = (b, nt, h)
    idx = (jnp.broadcast_to(jnp.asarray(layer, jnp.int32), rows),
           jnp.broadcast_to(phys[:, :, None], rows),
           jnp.broadcast_to(jnp.arange(h), rows),
           jnp.broadcast_to((start % bs // t)[:, :, None], rows))
    merged = jnp.where(put, vals, tiles.at[idx].get(mode="clip"))
    return tiles.at[idx].set(merged, mode="drop").reshape(pages.shape)


def _tile_rows(pages: jnp.ndarray) -> int:
    """Positions per written tile: the rows of one (8, 128) TPU memory
    tile, packed 4 // itemsize to a 32-bit word (8 f32, 16 bf16, 32 int8
    rows), so a tile is written whole, with no read-modify-write of
    packed words, and with 8-16 times fewer writes than one per row.
    Scale pages hold positions on the lane axis: a tile is a head's
    whole row."""
    bs = pages.shape[3]
    if pages.ndim == 4:
        return bs
    return math.gcd(bs, 8 * 4 // pages.dtype.itemsize)


def paged_view(pages: jnp.ndarray, block_tables: jnp.ndarray,
               layer=0) -> jnp.ndarray:
    """Gather each slot's logical KV view (B, max_blocks*bs, H, ...) of
    layer ``layer`` from stacked head-major (L, NB, H, bs, ...) pages.
    Unassigned table entries gather the null block; those positions sit
    past kv_len and are masked to NEG_INF before the softmax, so their
    (finite) garbage never contributes."""
    v = jnp.swapaxes(pages[layer, block_tables], 2, 3)  # (B, MB, bs, H, ..)
    return v.reshape((v.shape[0], v.shape[1] * v.shape[2]) + v.shape[3:])


_PAGE_OPERANDS = ("k_pages", "v_pages", "k2_pages", "k_scales", "v_scales",
                  "k2_scales")


def fused_paged_attention(q, k_pages, v_pages, block_tables, base, *,
                          layer=0, lengths=None, q2=None, k2_pages=None,
                          k_scales=None, v_scales=None, k2_scales=None,
                          **kw):
    """``paged_decode_attention`` as the decode layers call it, on the
    stacked (L, ...) page leaves at ``layer``. Traced under a serving
    mesh (``shard_rules.activation_mesh``), the kernel runs per shard
    inside a ``shard_map`` — Mosaic kernels cannot be partitioned
    automatically — with the specs of ``shard_rules.paged_kernel_specs``;
    each shard attends its own slots and kv-heads, so per-slot results
    are those of one device."""
    b, c = q.shape[:2]
    if lengths is None:
        lengths = jnp.full((b,), c, jnp.int32)
    ops = {"q": q, "k_pages": k_pages, "v_pages": v_pages,
           "block_tables": block_tables, "positions": base,
           "lengths": lengths, "layer": jnp.asarray(layer, jnp.int32),
           "q2": q2,
           "k2_pages": k2_pages, "k_scales": k_scales, "v_scales": v_scales,
           "k2_scales": k2_scales}
    ops = {k: v for k, v in ops.items() if v is not None}
    mesh = shard_rules.traced_serving_mesh()
    if mesh is None:
        return paged_decode_attention(**{"v_pages": None, **ops}, **kw)
    if shard_rules.serving_degrees(mesh)[0] > 1:
        # Pages may be split over 'data' and the kernel reads them whole:
        # gather this layer's pages, not every layer's.
        for k in _PAGE_OPERANDS:
            if k in ops:
                ops[k] = jax.lax.dynamic_slice_in_dim(ops[k], layer, 1)
        ops["layer"] = jnp.int32(0)
    per_slot, pages, rows, out = shard_rules.paged_kernel_specs(
        mesh, b, q.shape[2], k_pages.shape[2])
    spec = {"q": per_slot, "q2": per_slot, "block_tables": rows,
            "positions": rows, "lengths": rows, "layer": P()}
    return jax.shard_map(
        lambda *xs: paged_decode_attention(
            **{"v_pages": None, **dict(zip(ops, xs))}, **kw),
        mesh=mesh, out_specs=out, check_vma=False,
        in_specs=tuple(spec.get(k, pages) for k in ops))(*ops.values())


def decode_positions(position, batch: int, width: int) -> jnp.ndarray:
    """(B, C) absolute position matrix for a decode chunk: ``position`` is
    a scalar or (B,) *base*; chunk entry i sits at base + i. C == 1
    reduces to the classic per-slot position vector."""
    p = jnp.asarray(position)
    base = jnp.broadcast_to(p, (batch,)) if p.ndim == 0 else p.reshape(batch)
    return base[:, None] + jnp.arange(width)


def gqa_decode(p: Params, cfg: ModelConfig, x: jnp.ndarray,
               position, cache: Dict, *, fmt: str = "none",
               impl: str = "ref", interpret: bool = True,
               mrope_positions=None, cross: bool = False,
               block_tables=None, lengths=None,
               paged_impl: str = "fused", layer=0):
    """Decode step over a chunk of C tokens. x: (B, C, d); ``position``:
    scalar int32 or (B,) base position (chunk entry i sits at base + i);
    cache {"k","v"}: (B, S, Hkv, D) pre-allocated — or, when
    ``block_tables`` (B, max_blocks) is passed, the arena's stacked
    head-major pages (L, NB, Hkv, bs, D), of which this call writes and
    reads layer ``layer`` in place.
    C == 1 is the classic one-token step. Returns (out, cache).

    ``lengths``: (B,) valid entries per row (chunked prefill: a slot may
    fill only part of the chunk) — writes past a row's length are dropped
    and its tail outputs are garbage the engine never reads.

    ``paged_impl``: "fused" attends the physical pages in place through
    the block-table-native Pallas kernel (no per-step gather); "ref"
    keeps the ``paged_view`` dense-gather oracle.

    ``cross``: whisper cross-attention — attend to a static encoder cache
    without inserting (cross caches stay per-slot, never paged)."""
    b, cw = x.shape[:2]
    hd = cfg.resolved_head_dim()
    pos_mat = decode_positions(position, b, cw)
    q, k, v = _project_qkv(p, cfg, x, pos_mat, fmt, impl, interpret,
                           mrope_positions)
    if cross:
        kc, vc = cache["k"], cache["v"]
        kv_len = None
    elif block_tables is not None:
        kv_quant = isinstance(cache["k"], dict)   # {"q","s"} int8 leaves
        insert = _paged_insert_quant if kv_quant else paged_insert_token
        kp = insert(cache["k"], k, position, block_tables, lengths, layer)
        vp = insert(cache["v"], v, position, block_tables, lengths, layer)
        cache = {"k": kp, "v": vp}
        if paged_impl == "fused":
            base = jnp.broadcast_to(jnp.asarray(position, jnp.int32), (b,))
            if kv_quant:
                o = fused_paged_attention(
                    q, kp["q"], vp["q"], block_tables, base,
                    sm_scale=hd ** -0.5, layer=layer, k_scales=kp["s"],
                    v_scales=vp["s"], lengths=lengths,
                    interpret=interpret)
            else:
                o = fused_paged_attention(q, kp, vp, block_tables, base,
                                          sm_scale=hd ** -0.5, layer=layer,
                                          lengths=lengths,
                                          interpret=interpret)
            o = o.reshape(b, cw, cfg.num_heads * hd)
            out = layers.linear_apply(p["o"], o, fmt, impl=impl,
                                      interpret=interpret)
            return out, cache
        if kv_quant:
            kc = _paged_view_dequant(kp, block_tables, layer).astype(q.dtype)
            vc = _paged_view_dequant(vp, block_tables, layer).astype(q.dtype)
        else:
            kc = paged_view(kp, block_tables, layer)
            vc = paged_view(vp, block_tables, layer)
        kv_len = pos_mat + 1                # per-query causal depth
    else:
        kc = _insert_kv(cache["k"], k, position, lengths)
        vc = _insert_kv(cache["v"], v, position, lengths)
        cache = {"k": kc, "v": vc}
        kv_len = pos_mat + 1 if cw > 1 or lengths is not None else position + 1
    o = decode_attention(q, kc, vc, sm_scale=hd ** -0.5, kv_len=kv_len)
    o = o.reshape(b, cw, cfg.num_heads * hd)
    out = layers.linear_apply(p["o"], o, fmt, impl=impl, interpret=interpret)
    return out, cache


def gqa_cache_shape(cfg: ModelConfig, batch: int, seq: int):
    hd = cfg.resolved_head_dim()
    return {"k": (batch, seq, cfg.num_kv_heads, hd),
            "v": (batch, seq, cfg.num_kv_heads, hd)}


# ----------------------------------------------------------------------
# MLA (DeepSeek-V3): low-rank Q/KV, decoupled RoPE, compressed cache
# ----------------------------------------------------------------------
def mla_init(key, cfg: ModelConfig, fmt: str = "none") -> Params:
    m = cfg.mla
    d = cfg.d_model
    h = cfg.num_heads
    ks = jax.random.split(key, 5)
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "q_a": layers.linear_init(ks[0], d, m.q_lora_rank, fmt),
        "q_a_norm": layers.rmsnorm_init(m.q_lora_rank),
        "q_b": layers.linear_init(ks[1], m.q_lora_rank, h * qk_dim, fmt),
        "kv_a": layers.linear_init(
            ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim, fmt),
        "kv_a_norm": layers.rmsnorm_init(m.kv_lora_rank),
        "kv_b": layers.linear_init(
            ks[3], m.kv_lora_rank,
            h * (m.qk_nope_head_dim + m.v_head_dim), fmt),
        "o": layers.linear_init(ks[4], h * m.v_head_dim, d, fmt),
    }


def _mla_qkv(p: Params, cfg: ModelConfig, x: jnp.ndarray,
             positions: jnp.ndarray, fmt, impl, interpret):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    la = lambda pp, xx: layers.linear_apply(pp, xx, fmt, impl=impl,
                                            interpret=interpret)
    qa = layers.rmsnorm_apply(p["q_a_norm"], la(p["q_a"], x), cfg.norm_eps)
    q = la(p["q_b"], qa).reshape(
        b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = layers.apply_rope(q_rope, positions, cfg.rope_theta)

    kv = la(p["kv_a"], x)
    ckv, krope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    ckv = layers.rmsnorm_apply(p["kv_a_norm"], ckv, cfg.norm_eps)
    krope = layers.apply_rope(krope[:, :, None, :], positions,
                              cfg.rope_theta)[:, :, 0, :]
    return q_nope, q_rope, ckv, krope


def mla_apply(p: Params, cfg: ModelConfig, x: jnp.ndarray,
              positions: jnp.ndarray, *, fmt: str = "none",
              impl: str = "ref", interpret: bool = True,
              kv_chunk: int = 1024) -> jnp.ndarray:
    """Train/prefill MLA: expand compressed KV per chunk, chunked attention."""
    m = cfg.mla
    h = cfg.num_heads
    b, s, _ = x.shape
    q_nope, q_rope, ckv, krope = _mla_qkv(p, cfg, x, positions, fmt, impl,
                                          interpret)
    kvb = layers.linear_apply(p["kv_b"], ckv, fmt, impl=impl,
                              interpret=interpret)
    kvb = kvb.reshape(b, s, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kvb, [m.qk_nope_head_dim], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope[:, :, None, :],
                                  (b, s, h, m.qk_rope_head_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    sm = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    o = chunked_attention(q, k, v, causal=True, sm_scale=sm,
                          kv_chunk=kv_chunk)
    o = o.reshape(b, s, h * m.v_head_dim)
    return layers.linear_apply(p["o"], o, fmt, impl=impl, interpret=interpret)


def mla_prefill(p, cfg, x, positions, *, fmt="none", impl="ref",
                interpret=True, kv_chunk: int = 1024):
    out = mla_apply(p, cfg, x, positions, fmt=fmt, impl=impl,
                    interpret=interpret, kv_chunk=kv_chunk)
    # Cache holds the *compressed* latents only (the MLA memory win).
    _, _, ckv, krope = _mla_qkv(p, cfg, x, positions, fmt, impl, interpret)
    return out, {"ckv": ckv, "krope": krope}


def mla_decode(p, cfg, x, position, cache, *, fmt="none", impl="ref",
               interpret=True, block_tables=None, lengths=None,
               paged_impl: str = "fused", layer=0):
    """Absorbed-matmul MLA decode over a chunk of C tokens: the kv_b
    projection is folded into the query/output sides so the compressed
    cache is attended directly — no (B, S, H, D) expansion ever
    materializes. C == 1 is the classic one-token step; ``position`` is
    the scalar/(B,) base and ``lengths`` the per-row valid count (chunked
    prefill), masking exactly like the GQA chunk path.

    With ``block_tables``, cache leaves are the arena's stacked physical
    pages (L, NB, 1, bs, ...) — a unit head axis, the paged layout's
    shared shape — and the compressed latents are scattered through the
    table into layer ``layer``, which is also the layer read. The
    default ``paged_impl="fused"`` attends the pages in place: the fused
    kernel takes the absorbed ``q_eff`` against the ckv pages (which are
    also V) and joins the decoupled-RoPE scores via its q2/k2 operands —
    compressed cache never expanded *and* never gathered. ``"ref"``
    keeps the ``paged_view`` dense-gather oracle."""
    m = cfg.mla
    h = cfg.num_heads
    b, cw = x.shape[:2]
    pos_mat = decode_positions(position, b, cw)
    q_nope, q_rope, ckv_new, krope_new = _mla_qkv(
        p, cfg, x, pos_mat, fmt, impl, interpret)
    fused = block_tables is not None and paged_impl == "fused"
    kv_quant = block_tables is not None and isinstance(cache["ckv"], dict)
    if block_tables is not None:
        insert = _paged_insert_quant if kv_quant else paged_insert_token
        view = _paged_view_dequant if kv_quant else paged_view
        ckv_p = insert(cache["ckv"], ckv_new[:, :, None], position,
                       block_tables, lengths, layer)
        krope_p = insert(cache["krope"], krope_new[:, :, None], position,
                         block_tables, lengths, layer)
        cache = {"ckv": ckv_p, "krope": krope_p}
        if not fused:
            ckv = view(ckv_p, block_tables, layer)[:, :, 0]
            krope = view(krope_p, block_tables, layer)[:, :, 0]
    else:
        ckv = _insert_kv(cache["ckv"], ckv_new, position, lengths)
        krope = _insert_kv(cache["krope"], krope_new, position, lengths)
        cache = {"ckv": ckv, "krope": krope}

    wkv = layers.linear_dense_weight(p["kv_b"], fmt, dtype=jnp.float32)
    wkv = wkv[:, :m.kv_lora_rank]      # drop K-quant padding columns
    wkv = wkv.reshape(h, m.qk_nope_head_dim + m.v_head_dim, m.kv_lora_rank)
    wk = wkv[:, :m.qk_nope_head_dim]                    # (h, nope, rank)
    wv = wkv[:, m.qk_nope_head_dim:]                    # (h, v, rank)

    qn = q_nope.astype(jnp.float32)                     # (b, q, h, nope)
    q_eff = jnp.einsum("bqhc,hcr->bqhr", qn, wk)        # (b, q, h, rank)
    sm = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if fused:
        base = jnp.broadcast_to(jnp.asarray(position, jnp.int32), (b,))
        if kv_quant:
            # Quantized compressed latents: int8 code pages + (L, NP, 1,
            # bs) scale pages — already the kernel's Hkv == 1 layout.
            ctx = fused_paged_attention(
                q_eff, ckv_p["q"], None,                 # ckv doubles as V
                block_tables, base, sm_scale=sm, layer=layer,
                q2=q_rope.astype(jnp.float32), k2_pages=krope_p["q"],
                k_scales=ckv_p["s"], k2_scales=krope_p["s"],
                lengths=lengths, out_dtype=jnp.float32, interpret=interpret)
        else:
            ctx = fused_paged_attention(
                q_eff, ckv_p, None,                      # ckv doubles as V
                block_tables, base, sm_scale=sm, layer=layer,
                q2=q_rope.astype(jnp.float32), k2_pages=krope_p,
                lengths=lengths, out_dtype=jnp.float32,
                interpret=interpret)                     # (b,q,h,rank)
        o = jnp.einsum("bqhr,hvr->bqhv", ctx, wv)
        o = o.reshape(b, cw, h * m.v_head_dim).astype(x.dtype)
        out = layers.linear_apply(p["o"], o, fmt, impl=impl,
                                  interpret=interpret)
        return out, cache
    if flags.mixed_intermediates():
        s_nope = jnp.einsum("bqhr,bsr->bhqs", q_eff.astype(ckv.dtype), ckv,
                            preferred_element_type=jnp.float32)
        s_rope = jnp.einsum("bqhe,bse->bhqs",
                            q_rope.astype(krope.dtype), krope,
                            preferred_element_type=jnp.float32)
        ckv_f = ckv
    else:
        ckv_f = ckv.astype(jnp.float32)
        s_nope = jnp.einsum("bqhr,bsr->bhqs", q_eff, ckv_f)
        s_rope = jnp.einsum("bqhe,bse->bhqs",
                            q_rope.astype(jnp.float32),
                            krope.astype(jnp.float32))
    sc = (s_nope + s_rope) * sm                         # (b, h, q, s)
    slen = ckv.shape[1]
    kv_len = pos_mat + 1                                # per-query depth
    mask = jnp.arange(slen)[None, None, None, :] \
        < kv_len[:, None, :, None]
    sc = jnp.where(mask, sc, NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)                    # (b, h, q, s)
    ctx = jnp.einsum("bhqs,bsr->bqhr", pr.astype(ckv_f.dtype), ckv_f,
                     preferred_element_type=jnp.float32)  # (b, q, h, rank)
    o = jnp.einsum("bqhr,hvr->bqhv", ctx, wv)           # (b, q, h, v_dim)
    o = o.reshape(b, cw, h * m.v_head_dim).astype(x.dtype)
    out = layers.linear_apply(p["o"], o, fmt, impl=impl, interpret=interpret)
    return out, cache


def mla_cache_shape(cfg: ModelConfig, batch: int, seq: int):
    m = cfg.mla
    return {"ckv": (batch, seq, m.kv_lora_rank),
            "krope": (batch, seq, m.qk_rope_head_dim)}
