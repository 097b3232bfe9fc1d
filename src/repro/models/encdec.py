"""Whisper-style encoder/decoder (audio family; conv frontend stubbed).

Per the assignment the mel/conv frontend is a STUB: ``input_specs()``
provides precomputed frame embeddings (B, S_enc, d) that feed the encoder
directly. Positions are sinusoidal (whisper uses sinusoidal for the encoder
and learned for the decoder; we use sinusoidal for both so parameter shapes
stay independent of sequence length — noted in DESIGN.md).

Decoder KV caches: {"self": {k, v}, "cross": {k, v}} per layer; the cross
cache is computed once from the encoder output.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import flags
from repro.models import layers
from repro.models.layers import Params


def sinusoid_positions(seq: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    return pe[:, :d]


def _mlp_init(key, d: int, d_ff: int, fmt: str) -> Params:
    k1, k2 = jax.random.split(key)
    return {"fc1": layers.linear_init(k1, d, d_ff, fmt, bias=True),
            "fc2": layers.linear_init(k2, d_ff, d, fmt, bias=True)}


def _mlp_apply(p: Params, x, fmt, impl, interpret):
    h = layers.linear_apply(p["fc1"], x, fmt, impl=impl, interpret=interpret)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return layers.linear_apply(p["fc2"], h, fmt, impl=impl,
                               interpret=interpret)


def _enc_layer_init(key, cfg: ModelConfig, fmt: str) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "norm1": layers.layernorm_init(cfg.d_model),
        "attn": attn.gqa_init(k1, cfg, fmt),
        "norm2": layers.layernorm_init(cfg.d_model),
        "mlp": _mlp_init(k2, cfg.d_model, cfg.d_ff, fmt),
    }


def _dec_layer_init(key, cfg: ModelConfig, fmt: str) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "self_norm": layers.layernorm_init(cfg.d_model),
        "self_attn": attn.gqa_init(k1, cfg, fmt),
        "cross_norm": layers.layernorm_init(cfg.d_model),
        "cross_attn": attn.gqa_init(k2, cfg, fmt),
        "ffn_norm": layers.layernorm_init(cfg.d_model),
        "mlp": _mlp_init(k3, cfg.d_model, cfg.d_ff, fmt),
    }


def encdec_init(key, cfg: ModelConfig, quant: str = "none") -> Params:
    recipe = layers.recipe_for(quant)
    fmt, fmt_emb = recipe["linear"], recipe["embed"]
    ke, kenc, kdec = jax.random.split(key, 3)
    enc_keys = jax.random.split(kenc, cfg.encoder_layers)
    dec_keys = jax.random.split(kdec, cfg.num_layers)
    return {
        "embed": layers.embedding_init(ke, cfg.vocab_size, cfg.d_model,
                                       fmt_emb),
        "enc_layers": jax.vmap(
            lambda k: _enc_layer_init(k, cfg, fmt))(enc_keys),
        "enc_norm": layers.layernorm_init(cfg.d_model),
        "dec_layers": jax.vmap(
            lambda k: _dec_layer_init(k, cfg, fmt))(dec_keys),
        "dec_norm": layers.layernorm_init(cfg.d_model),
    }


def _cross_kv(p: Params, cfg: ModelConfig, enc_out, fmt, impl, interpret):
    b, s, _ = enc_out.shape
    hd = cfg.resolved_head_dim()
    k = layers.linear_apply(p["k"], enc_out, fmt, impl=impl,
                            interpret=interpret)
    v = layers.linear_apply(p["v"], enc_out, fmt, impl=impl,
                            interpret=interpret)
    return (k.reshape(b, s, cfg.num_kv_heads, hd),
            v.reshape(b, s, cfg.num_kv_heads, hd))


def _cross_attend(p: Params, cfg: ModelConfig, x, kv, fmt, impl, interpret,
                  kv_chunk=1024):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim()
    q = layers.linear_apply(p["q"], x, fmt, impl=impl, interpret=interpret)
    q = q.reshape(b, s, cfg.num_heads, hd)
    o = attn.chunked_attention(q, kv[0], kv[1], causal=False,
                               sm_scale=hd ** -0.5, kv_chunk=kv_chunk)
    o = o.reshape(b, s, cfg.num_heads * hd)
    return layers.linear_apply(p["o"], o, fmt, impl=impl,
                               interpret=interpret)


def encode(params, cfg: ModelConfig, frames: jnp.ndarray, *, quant="none",
           impl="ref", interpret=True, kv_chunk=1024) -> jnp.ndarray:
    fmt = layers.recipe_for(quant)["linear"]
    b, s, d = frames.shape
    h = frames.astype(jnp.bfloat16) + sinusoid_positions(s, d).astype(
        jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def body(h, lp):
        hn = layers.layernorm_apply(lp["norm1"], h)
        h = h + attn.gqa_apply(lp["attn"], cfg, hn, positions, fmt=fmt,
                               impl=impl, interpret=interpret, causal=False,
                               kv_chunk=kv_chunk)
        hn = layers.layernorm_apply(lp["norm2"], h)
        h = h + _mlp_apply(lp["mlp"], hn, fmt, impl, interpret)
        return h, None

    h, _ = jax.lax.scan(body, h, params["enc_layers"],
                        unroll=flags.inner_unroll())
    return layers.layernorm_apply(params["enc_norm"], h)


def encdec_forward(params, cfg: ModelConfig, batch: Dict, *, quant="none",
                   impl="ref", interpret=True, kv_chunk=1024,
                   remat: str = "none"):
    """batch: {"tokens": (B, S_dec), "frames": (B, S_enc, d)}."""
    recipe = layers.recipe_for(quant)
    fmt = recipe["linear"]
    enc_out = encode(params, cfg, batch["frames"], quant=quant, impl=impl,
                     interpret=interpret, kv_chunk=kv_chunk)
    tokens = batch["tokens"]
    b, s = tokens.shape
    h = layers.embedding_lookup(params["embed"], tokens, recipe["embed"],
                                jnp.bfloat16, width=cfg.d_model)
    h = h + sinusoid_positions(s, cfg.d_model).astype(h.dtype)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def body(h, lp):
        hn = layers.layernorm_apply(lp["self_norm"], h)
        h = h + attn.gqa_apply(lp["self_attn"], cfg, hn, positions, fmt=fmt,
                               impl=impl, interpret=interpret, causal=True,
                               kv_chunk=kv_chunk)
        hn = layers.layernorm_apply(lp["cross_norm"], h)
        kv = _cross_kv(lp["cross_attn"], cfg, enc_out, fmt, impl, interpret)
        h = h + _cross_attend(lp["cross_attn"], cfg, hn, kv, fmt, impl,
                              interpret, kv_chunk)
        hn = layers.layernorm_apply(lp["ffn_norm"], h)
        h = h + _mlp_apply(lp["mlp"], hn, fmt, impl, interpret)
        return h, None

    if remat != "none":
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, params["dec_layers"],
                        unroll=flags.inner_unroll())
    h = layers.layernorm_apply(params["dec_norm"], h)
    logits = layers.embedding_logits(params["embed"], h, recipe["embed"],
                                     impl=impl, interpret=interpret)
    return logits, jnp.zeros((), jnp.float32)


def encdec_loss(params, cfg, batch, *, quant="none", impl="ref",
                interpret=True, kv_chunk=1024, remat="none"):
    logits, _ = encdec_forward(params, cfg, batch, quant=quant, impl=impl,
                               interpret=interpret, kv_chunk=kv_chunk,
                               remat=remat)
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def encdec_prefill(params, cfg: ModelConfig, batch: Dict, *, quant="none",
                   impl="ref", interpret=True, kv_chunk=1024):
    """Encode + decoder prefill. Cache: per-layer self KV + static cross KV."""
    recipe = layers.recipe_for(quant)
    fmt = recipe["linear"]
    enc_out = encode(params, cfg, batch["frames"], quant=quant, impl=impl,
                     interpret=interpret, kv_chunk=kv_chunk)
    tokens = batch["tokens"]
    b, s = tokens.shape
    hd = cfg.resolved_head_dim()
    h = layers.embedding_lookup(params["embed"], tokens, recipe["embed"],
                                jnp.bfloat16, width=cfg.d_model)
    h = h + sinusoid_positions(s, cfg.d_model).astype(h.dtype)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def body(h, lp):
        hn = layers.layernorm_apply(lp["self_norm"], h)
        mix, self_cache = attn.gqa_prefill(
            lp["self_attn"], cfg, hn, positions, fmt=fmt, impl=impl,
            interpret=interpret, kv_chunk=kv_chunk)
        h = h + mix
        hn = layers.layernorm_apply(lp["cross_norm"], h)
        kv = _cross_kv(lp["cross_attn"], cfg, enc_out, fmt, impl, interpret)
        h = h + _cross_attend(lp["cross_attn"], cfg, hn, kv, fmt, impl,
                              interpret, kv_chunk)
        hn = layers.layernorm_apply(lp["ffn_norm"], h)
        h = h + _mlp_apply(lp["mlp"], hn, fmt, impl, interpret)
        return h, {"self": self_cache, "cross": {"k": kv[0], "v": kv[1]}}

    h, cache = jax.lax.scan(body, h, params["dec_layers"],
                            unroll=flags.inner_unroll())
    h = layers.layernorm_apply(params["dec_norm"], h)
    logits = layers.embedding_logits(params["embed"], h[:, -1:],
                                     recipe["embed"], impl=impl,
                                     interpret=interpret)
    return logits, {"dec_layers": cache}


def encdec_encode_cross(params, cfg: ModelConfig, frames, *, quant="none",
                        impl="ref", interpret=True, kv_chunk=1024):
    """Admission-time encoder pass for the unified chunked-prefill engine:
    run the encoder once and project the per-layer cross K/V, returning a
    cache pytree shaped like ``encdec_cache_shapes`` with the self-KV
    leaves as minimal (seq=1) zero stubs — the engine scatters it into an
    arena slot and the decoder prompt then streams through the decode
    step chunk by chunk (no bucketed decoder prefill pass)."""
    fmt = layers.recipe_for(quant)["linear"]
    enc_out = encode(params, cfg, frames, quant=quant, impl=impl,
                     interpret=interpret, kv_chunk=kv_chunk)
    b = frames.shape[0]
    hd = cfg.resolved_head_dim()

    def body(carry, lp):
        kv = _cross_kv(lp["cross_attn"], cfg, enc_out, fmt, impl, interpret)
        return carry, {"k": kv[0], "v": kv[1]}

    _, cross = jax.lax.scan(body, None, params["dec_layers"],
                            unroll=flags.inner_unroll())
    L = cfg.num_layers
    zero_self = jnp.zeros((L, b, 1, cfg.num_kv_heads, hd), jnp.bfloat16)
    return {"dec_layers": {"self": {"k": zero_self, "v": zero_self},
                           "cross": cross}}


def encdec_decode_step(params, cfg: ModelConfig, token, position, cache, *,
                       quant="none", impl="ref", interpret=True,
                       block_tables=None, lengths=None, paged_impl="fused"):
    """Decode step over a chunk of C tokens (C == 1 classic).
    ``block_tables``: paged-arena tables for the decoder *self*-attn KV
    (the cross KV is a constant-size per-slot state — never paged);
    ``paged_impl`` selects the fused block-table kernel or the gather
    oracle. ``lengths``: (B,) valid chunk entries per row."""
    recipe = layers.recipe_for(quant)
    fmt = recipe["linear"]
    b, cw = token.shape
    hd = cfg.resolved_head_dim()
    h = layers.embedding_lookup(params["embed"], token, recipe["embed"],
                                jnp.bfloat16, width=cfg.d_model)
    pe = sinusoid_positions(cfg.max_seq_len, cfg.d_model)
    # position: scalar or (B,) base — chunk entry i sits at base + i
    # (gather clamps any invalid-tail overrun; those rows are discarded).
    pos_mat = attn.decode_positions(position, b, cw)
    h = h + pe[pos_mat].astype(h.dtype)

    # Paged self-attention takes a stack of layers: this scan hands it one
    # layer's pages as a stack of one.
    paged = block_tables is not None
    stack = lambda c: jax.tree.map(lambda x: x[None], c) if paged else c
    unstack = lambda c: jax.tree.map(lambda x: x[0], c) if paged else c

    def body(h, xs):
        lp, lc = xs
        hn = layers.layernorm_apply(lp["self_norm"], h)
        mix, self_cache = attn.gqa_decode(
            lp["self_attn"], cfg, hn, position, stack(lc["self"]), fmt=fmt,
            impl=impl, interpret=interpret, block_tables=block_tables,
            lengths=lengths, paged_impl=paged_impl)
        self_cache = unstack(self_cache)
        h = h + mix
        hn = layers.layernorm_apply(lp["cross_norm"], h)
        q = layers.linear_apply(lp["cross_attn"]["q"], hn, fmt, impl=impl,
                                interpret=interpret)
        q = q.reshape(b, cw, cfg.num_heads, hd)
        o = attn.decode_attention(q, lc["cross"]["k"], lc["cross"]["v"],
                                  sm_scale=hd ** -0.5)
        o = o.reshape(b, cw, cfg.num_heads * hd)
        h = h + layers.linear_apply(lp["cross_attn"]["o"], o, fmt, impl=impl,
                                    interpret=interpret)
        hn = layers.layernorm_apply(lp["ffn_norm"], h)
        h = h + _mlp_apply(lp["mlp"], hn, fmt, impl, interpret)
        return h, {"self": self_cache, "cross": lc["cross"]}

    h, new_cache = jax.lax.scan(body, h,
                                (params["dec_layers"], cache["dec_layers"]),
                                unroll=flags.inner_unroll())
    h = layers.layernorm_apply(params["dec_norm"], h)
    logits = layers.embedding_logits(params["embed"], h, recipe["embed"],
                                     impl=impl, interpret=interpret)
    return logits, {"dec_layers": new_cache}


def encdec_cache_shapes(cfg: ModelConfig, batch: int, seq: int) -> Dict:
    hd = cfg.resolved_head_dim()
    L = cfg.num_layers
    return {"dec_layers": {
        "self": {"k": (L, batch, seq, cfg.num_kv_heads, hd),
                 "v": (L, batch, seq, cfg.num_kv_heads, hd)},
        "cross": {"k": (L, batch, cfg.encoder_seq_len, cfg.num_kv_heads, hd),
                  "v": (L, batch, cfg.encoder_seq_len, cfg.num_kv_heads, hd)},
    }}
