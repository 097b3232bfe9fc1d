"""Decoder-only LM assembly for dense / moe / vlm / ssm / hybrid families.

Layers are stacked (leading L dim) and driven by ``lax.scan`` so the HLO
stays compact for 126-layer models; non-uniform stacks (deepseek's dense
prefix, jamba's period-8 pattern) scan over their own groups.

Each family provides: init / forward (train+loss) / prefill / decode_step /
cache shapes. Quantization (`quant` recipe name) is a *static* argument that
determines the parameter pytree structure (plane dicts) — the same functions
serve both bf16 training and quantized inference.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import flags
from repro.models import layers, moe, ssm
from repro.models.layers import Params


# ----------------------------------------------------------------------
# Generic layer = pre-norm mixer + pre-norm FFN
# ----------------------------------------------------------------------
def _mixer_kind(cfg: ModelConfig, li: int) -> str:
    if cfg.family == "ssm":
        return "ssm"
    if cfg.family == "hybrid":
        return "gqa" if (li % cfg.attn_period) == cfg.attn_offset else "ssm"
    if cfg.mla is not None:
        return "mla"
    return "gqa"


def _ffn_kind(cfg: ModelConfig, li: int) -> str:
    if cfg.family == "ssm":
        return "none"
    if cfg.family == "hybrid":
        return "moe" if (cfg.moe_period and li % cfg.moe_period == 1) \
            else "dense"
    if cfg.moe is not None:
        return "moe" if li >= cfg.moe.first_dense_layers else "dense"
    return "dense"


def layer_init(key, cfg: ModelConfig, mixer: str, ffn: str,
               fmt: str) -> Params:
    k1, k2 = jax.random.split(key)
    p: Params = {"mixer_norm": layers.rmsnorm_init(cfg.d_model)}
    if mixer == "gqa":
        p["attn"] = attn.gqa_init(k1, cfg, fmt)
    elif mixer == "mla":
        p["attn"] = attn.mla_init(k1, cfg, fmt)
    else:
        p["ssm"] = ssm.ssm_init(k1, cfg, fmt)
    if ffn != "none":
        p["ffn_norm"] = layers.rmsnorm_init(cfg.d_model)
        if ffn == "moe":
            p["ffn"] = moe.moe_init(k2, cfg, fmt)
        else:
            dff = cfg.d_ff
            if cfg.moe is not None and cfg.moe.dense_d_ff and ffn == "dense":
                dff = cfg.moe.dense_d_ff
            p["ffn"] = layers.swiglu_init(k2, cfg.d_model, dff, fmt)
    return p


def layer_apply(p: Params, cfg: ModelConfig, h, positions, *, mixer, ffn,
                fmt, impl, interpret, kv_chunk, mrope_positions=None):
    """Full-sequence layer (train). Returns (h, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    hn = layers.rmsnorm_apply(p["mixer_norm"], h, cfg.norm_eps)
    if mixer == "gqa":
        mix = attn.gqa_apply(p["attn"], cfg, hn, positions, fmt=fmt,
                             impl=impl, interpret=interpret,
                             kv_chunk=kv_chunk,
                             mrope_positions=mrope_positions)
    elif mixer == "mla":
        mix = attn.mla_apply(p["attn"], cfg, hn, positions, fmt=fmt,
                             impl=impl, interpret=interpret,
                             kv_chunk=kv_chunk)
    else:
        mix = ssm.ssm_apply(p["ssm"], cfg, hn, fmt=fmt, impl=impl,
                            interpret=interpret)
    h = h + mix
    if ffn != "none":
        hn = layers.rmsnorm_apply(p["ffn_norm"], h, cfg.norm_eps)
        if ffn == "moe":
            y, aux = moe.moe_apply(p["ffn"], cfg, hn, fmt=fmt, impl=impl,
                                   interpret=interpret)
        else:
            y = layers.swiglu_apply(p["ffn"], hn, fmt, impl=impl,
                                    interpret=interpret)
        h = h + y
    return h, aux


def layer_prefill(p: Params, cfg: ModelConfig, h, positions, *, mixer, ffn,
                  fmt, impl, interpret, kv_chunk, mrope_positions=None):
    """Returns (h, cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    hn = layers.rmsnorm_apply(p["mixer_norm"], h, cfg.norm_eps)
    if mixer == "gqa":
        mix, cache = attn.gqa_prefill(p["attn"], cfg, hn, positions, fmt=fmt,
                                      impl=impl, interpret=interpret,
                                      kv_chunk=kv_chunk,
                                      mrope_positions=mrope_positions)
    elif mixer == "mla":
        mix, cache = attn.mla_prefill(p["attn"], cfg, hn, positions, fmt=fmt,
                                      impl=impl, interpret=interpret,
                                      kv_chunk=kv_chunk)
    else:
        mix, cache = ssm.ssm_apply(p["ssm"], cfg, hn, fmt=fmt, impl=impl,
                                   interpret=interpret, return_state=True)
    h = h + mix
    if ffn != "none":
        hn = layers.rmsnorm_apply(p["ffn_norm"], h, cfg.norm_eps)
        if ffn == "moe":
            y, aux = moe.moe_apply(p["ffn"], cfg, hn, fmt=fmt, impl=impl,
                                   interpret=interpret)
        else:
            y = layers.swiglu_apply(p["ffn"], hn, fmt, impl=impl,
                                    interpret=interpret)
        h = h + y
    return h, cache, aux


def layer_decode(p: Params, cfg: ModelConfig, h, position, cache, layer, *,
                 mixer, ffn, fmt, impl, interpret, mrope_positions=None,
                 block_tables=None, lengths=None, paged_impl="fused"):
    """Decode layer step over a chunk of C tokens (C == 1 is the classic
    one-token step). Returns (h, new_cache). ``block_tables``:
    paged-arena tables threaded to the attention mixers (SSM states are
    per-slot constants — paging does not apply); ``paged_impl`` selects
    the fused block-table kernel or the gather oracle. ``lengths``: (B,)
    valid chunk entries per row (unified chunked prefill).

    ``cache`` is this layer's own slice, or — for an attention mixer
    with ``block_tables`` — its group's stacked pages, which the mixer
    writes and reads at ``layer`` in place (``_in_place``)."""
    hn = layers.rmsnorm_apply(p["mixer_norm"], h, cfg.norm_eps)
    if mixer == "gqa":
        mix, cache = attn.gqa_decode(p["attn"], cfg, hn, position, cache,
                                     fmt=fmt, impl=impl, interpret=interpret,
                                     mrope_positions=mrope_positions,
                                     block_tables=block_tables,
                                     lengths=lengths, paged_impl=paged_impl,
                                     layer=layer)
    elif mixer == "mla":
        mix, cache = attn.mla_decode(p["attn"], cfg, hn, position, cache,
                                     fmt=fmt, impl=impl, interpret=interpret,
                                     block_tables=block_tables,
                                     lengths=lengths, paged_impl=paged_impl,
                                     layer=layer)
    else:
        mix, cache = ssm.ssm_decode(p["ssm"], cfg, hn, cache, fmt=fmt,
                                    impl=impl, interpret=interpret,
                                    lengths=lengths)
    h = h + mix
    if ffn != "none":
        hn = layers.rmsnorm_apply(p["ffn_norm"], h, cfg.norm_eps)
        if ffn == "moe":
            y, _ = moe.moe_apply(p["ffn"], cfg, hn, fmt=fmt, impl=impl,
                                 interpret=interpret)
        else:
            y = layers.swiglu_apply(p["ffn"], hn, fmt, impl=impl,
                                    interpret=interpret)
        h = h + y
    return h, cache


def _in_place(mixer: str, block_tables) -> bool:
    """Whether a mixer's cache is paged and so stays stacked in the
    decode scan's carry: attention leaves of a paged arena. Per-slot
    leaves (contiguous K/V, SSM state) are the scan's ``xs``/``ys``,
    which hand each layer its slice and take back whatever dtype it
    emits (the f32 SSM state ``kvcache.step_leaf_dtypes`` probes)."""
    return block_tables is not None and mixer != "ssm"


def layer_cache_shape(cfg: ModelConfig, mixer: str, batch: int, seq: int):
    if mixer == "gqa":
        return attn.gqa_cache_shape(cfg, batch, seq)
    if mixer == "mla":
        return attn.mla_cache_shape(cfg, batch, seq)
    return ssm.ssm_cache_shape(cfg, batch)


# ----------------------------------------------------------------------
# Layer grouping: contiguous runs of identical (mixer, ffn) signatures
# become one stacked scan group; jamba's period-8 pattern becomes a scan
# over blocks of 8 distinct sub-layers.
# ----------------------------------------------------------------------
def layer_groups(cfg: ModelConfig):
    """Returns list of (group_name, count, [(mixer, ffn), ...per sub-layer])."""
    if cfg.family == "hybrid":
        period = cfg.attn_period
        assert cfg.num_layers % period == 0
        subs = [( _mixer_kind(cfg, i), _ffn_kind(cfg, i))
                for i in range(period)]
        return [("blocks", cfg.num_layers // period, subs)]
    sigs = [(_mixer_kind(cfg, i), _ffn_kind(cfg, i))
            for i in range(cfg.num_layers)]
    groups = []
    start = 0
    for i in range(1, cfg.num_layers + 1):
        if i == cfg.num_layers or sigs[i] != sigs[start]:
            groups.append((f"layers{len(groups)}", i - start, [sigs[start]]))
            start = i
    return groups


def _stack_init(key, count: int, one_init):
    keys = jax.random.split(key, count)
    return jax.vmap(one_init)(keys)


# ----------------------------------------------------------------------
# Model: init / forward / prefill / decode
# ----------------------------------------------------------------------
def lm_init(key, cfg: ModelConfig, quant: str = "none") -> Params:
    recipe = layers.recipe_for(quant)
    fmt_lin, fmt_emb = recipe["linear"], recipe["embed"]
    kemb, klay, khead = jax.random.split(key, 3)
    params: Params = {
        "embed": layers.embedding_init(kemb, cfg.vocab_size, cfg.d_model,
                                       fmt_emb),
        "final_norm": layers.rmsnorm_init(cfg.d_model),
    }
    groups = layer_groups(cfg)
    gkeys = jax.random.split(klay, len(groups))
    for gk, (name, count, subs) in zip(gkeys, groups):
        def one(k, subs=subs):
            sks = jax.random.split(k, len(subs))
            if len(subs) == 1:
                return layer_init(sks[0], cfg, subs[0][0], subs[0][1],
                                  fmt_lin)
            return {f"sub{i}": layer_init(sk, cfg, mx, ff, fmt_lin)
                    for i, (sk, (mx, ff)) in enumerate(zip(sks, subs))}
        params[name] = _stack_init(gk, count, one)
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.linear_init(
            khead, cfg.d_model, cfg.vocab_size, fmt_emb)
    return params


def _mrope_positions(cfg: ModelConfig, batch: int, seq: int):
    """Deterministic stub M-RoPE position grid: vision tokens get a
    (t=0, h, w) raster; text tokens advance temporally after the image."""
    v = min(cfg.vision_tokens, seq)
    side = max(int(v ** 0.5), 1)
    idx = jnp.arange(seq)
    is_vis = idx < v
    t_pos = jnp.where(is_vis, 0, idx - v + side)
    h_pos = jnp.where(is_vis, idx // side, idx - v + side)
    w_pos = jnp.where(is_vis, idx % side, idx - v + side)
    pos3 = jnp.stack([t_pos, h_pos, w_pos], axis=-1)       # (S, 3)
    return jnp.broadcast_to(pos3[None], (batch, seq, 3))


def _embed_inputs(params, cfg: ModelConfig, batch: Dict, quant: str,
                  dtype=jnp.bfloat16):
    recipe = layers.recipe_for(quant)
    tokens = batch["tokens"]
    b, s = tokens.shape
    h = layers.embedding_lookup(params["embed"], tokens, recipe["embed"],
                                dtype, width=cfg.d_model)
    if cfg.family == "vlm" and "vision_embeds" in batch:
        # Clip the vision prefix to the sequence actually being embedded:
        # a prefill bucket shorter than the vision grid (short prompts)
        # must not widen the sequence past the position vectors (the
        # qwen2-vl apply_mrope shape crash).
        v = min(batch["vision_embeds"].shape[1], s)
        h = jnp.concatenate([batch["vision_embeds"][:, :v].astype(dtype),
                             h[:, v:]], axis=1)
    return h


def _lm_head(params, cfg: ModelConfig, h, quant: str, impl, interpret):
    recipe = layers.recipe_for(quant)
    if cfg.tie_embeddings:
        return layers.embedding_logits(params["embed"], h, recipe["embed"],
                                       impl=impl, interpret=interpret)
    return layers.linear_apply(params["lm_head"], h, recipe["embed"],
                               impl=impl, interpret=interpret)


def lm_forward(params: Params, cfg: ModelConfig, batch: Dict, *,
               quant: str = "none", impl: str = "ref",
               interpret: bool = True, kv_chunk: int = 1024,
               remat: str = "none",
               act_sharding=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward. Returns (logits, aux_loss)."""
    recipe = layers.recipe_for(quant)
    fmt = recipe["linear"]
    tokens = batch["tokens"]
    b, s = tokens.shape
    h = _embed_inputs(params, cfg, batch, quant)
    if act_sharding is not None:
        h = jax.lax.with_sharding_constraint(h, act_sharding)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    mrope_pos = _mrope_positions(cfg, b, s) if cfg.mrope else None
    aux_total = jnp.zeros((), jnp.float32)

    for name, count, subs in layer_groups(cfg):
        def body(h, lp, subs=subs):
            aux_g = jnp.zeros((), jnp.float32)
            if len(subs) == 1:
                h, aux = layer_apply(lp, cfg, h, positions, mixer=subs[0][0],
                                     ffn=subs[0][1], fmt=fmt, impl=impl,
                                     interpret=interpret, kv_chunk=kv_chunk,
                                     mrope_positions=mrope_pos)
                aux_g += aux
            else:
                for i, (mx, ff) in enumerate(subs):
                    h, aux = layer_apply(lp[f"sub{i}"], cfg, h, positions,
                                         mixer=mx, ffn=ff, fmt=fmt,
                                         impl=impl, interpret=interpret,
                                         kv_chunk=kv_chunk,
                                         mrope_positions=mrope_pos)
                    aux_g += aux
            if act_sharding is not None:
                h = jax.lax.with_sharding_constraint(h, act_sharding)
            return h, aux_g
        if remat != "none":
            body = jax.checkpoint(
                body,
                policy=(jax.checkpoint_policies.dots_saveable
                        if remat == "dots_saveable" else None))
        h, auxs = jax.lax.scan(body, h, params[name],
                                unroll=flags.inner_unroll())
        aux_total += jnp.sum(auxs)

    h = layers.rmsnorm_apply(params["final_norm"], h, cfg.norm_eps)
    logits = _lm_head(params, cfg, h, quant, impl, interpret)
    return logits, aux_total


def lm_loss(params, cfg: ModelConfig, batch: Dict, *, quant="none",
            impl="ref", interpret=True, kv_chunk=1024,
            remat="none", act_sharding=None) -> jnp.ndarray:
    logits, aux = lm_forward(params, cfg, batch, quant=quant, impl=impl,
                             interpret=interpret, kv_chunk=kv_chunk,
                             remat=remat, act_sharding=act_sharding)
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    ce = jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    coef = cfg.moe.router_aux_coef if cfg.moe is not None else 0.0
    return ce + coef * aux


def lm_prefill(params, cfg: ModelConfig, batch: Dict, *, quant="none",
               impl="ref", interpret=True, kv_chunk=1024,
               act_sharding=None):
    """Prefill: returns (last-token logits, cache pytree)."""
    recipe = layers.recipe_for(quant)
    fmt = recipe["linear"]
    tokens = batch["tokens"]
    b, s = tokens.shape
    h = _embed_inputs(params, cfg, batch, quant)
    if act_sharding is not None:
        h = jax.lax.with_sharding_constraint(h, act_sharding)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    mrope_pos = _mrope_positions(cfg, b, s) if cfg.mrope else None
    caches = {}
    for name, count, subs in layer_groups(cfg):
        def body(h, lp, subs=subs):
            if len(subs) == 1:
                h, cache, _ = layer_prefill(
                    lp, cfg, h, positions, mixer=subs[0][0], ffn=subs[0][1],
                    fmt=fmt, impl=impl, interpret=interpret,
                    kv_chunk=kv_chunk, mrope_positions=mrope_pos)
            else:
                cache = {}
                for i, (mx, ff) in enumerate(subs):
                    h, c, _ = layer_prefill(
                        lp[f"sub{i}"], cfg, h, positions, mixer=mx, ffn=ff,
                        fmt=fmt, impl=impl, interpret=interpret,
                        kv_chunk=kv_chunk, mrope_positions=mrope_pos)
                    cache[f"sub{i}"] = c
            return h, cache
        h, cache = jax.lax.scan(body, h, params[name],
                                 unroll=flags.inner_unroll())
        caches[name] = cache
    h = layers.rmsnorm_apply(params["final_norm"], h, cfg.norm_eps)
    logits = _lm_head(params, cfg, h[:, -1:], quant, impl, interpret)
    return logits, caches


def _mrope_decode_positions(cfg: ModelConfig, pos_mat: jnp.ndarray):
    """(B, C, 3) M-RoPE positions for absolute positions ``pos_mat``
    (B, C): vision positions (< vision_tokens) get the (t=0, h, w) raster,
    text positions advance all three streams together — the same mapping
    ``_mrope_positions`` applies at prefill, evaluated pointwise so a
    decode chunk can span the vision/text boundary."""
    v = cfg.vision_tokens
    side = max(int(v ** 0.5), 1)
    is_vis = pos_mat < v
    txt = pos_mat - v + side
    t_pos = jnp.where(is_vis, 0, txt)
    h_pos = jnp.where(is_vis, pos_mat // side, txt)
    w_pos = jnp.where(is_vis, pos_mat % side, txt)
    return jnp.stack([t_pos, h_pos, w_pos], axis=-1)


def lm_decode_step(params, cfg: ModelConfig, token: jnp.ndarray,
                   position, cache, *, quant="none", impl="ref",
                   interpret=True, block_tables=None, lengths=None,
                   paged_impl="fused", embeds=None, embeds_mask=None):
    """token: (B, C) int32 — C == 1 is the classic one-token step, C > 1
    a chunk of consecutive tokens (unified chunked prefill); position:
    scalar int32 (lockstep batch) or (B,) int32 base positions (per-slot
    arena depths; chunk entry i sits at base + i); cache from prefill or
    ``lm_cache_shapes``. Returns (logits (B, C, V), new_cache).

    ``block_tables``: (B, max_blocks) int32 — paged-arena mode: attention
    cache leaves are physical pages and K/V are read through the table
    (see ``PagedKVArena``); ``paged_impl`` picks the fused block-table
    Pallas kernel ("fused", default) or the dense-gather oracle ("ref").

    ``lengths``: (B,) valid chunk entries per row — cache writes past a
    row's length are dropped, and its tail logits are garbage by contract
    (the engine samples at index ``lengths - 1``).

    ``embeds``/``embeds_mask``: (B, C, d) / (B, C) — vlm chunked prefill:
    positions flagged in the mask take the provided embedding (the stub
    vision patches) instead of the token embedding.

    The step's parts are named scopes (``embed``, ``layers``,
    ``lm_head``) in every op's metadata, so a device trace's ops map to
    the part of the model they run; names change no op."""
    recipe = layers.recipe_for(quant)
    fmt = recipe["linear"]
    b, cw = token.shape
    with jax.named_scope("embed"):
        h = layers.embedding_lookup(params["embed"], token, recipe["embed"],
                                    jnp.bfloat16, width=cfg.d_model)
        if embeds is not None:
            h = jnp.where(embeds_mask[..., None], embeds.astype(h.dtype), h)
    mrope_pos = None
    if cfg.mrope:
        pos_mat = attn.decode_positions(position, b, cw)
        mrope_pos = _mrope_decode_positions(cfg, pos_mat)
    new_caches = {}
    for name, count, subs in layer_groups(cfg):
        # Sub-layer caches by name (a one-sub group is its own "sub0").
        # Paged leaves ride the carry whole, so the arena is never sliced
        # per layer nor rebuilt as the scan's output; per-slot leaves are
        # sliced in and stacked out as before.
        keys = [f"sub{i}" for i in range(len(subs))]
        group = cache[name] if len(subs) > 1 else {"sub0": cache[name]}
        stacked = {k: group[k] for k, (mx, _) in zip(keys, subs)
                   if _in_place(mx, block_tables)}
        per_slot = {k: group[k] for k in keys if k not in stacked}

        def body(carry, xs, subs=subs, keys=keys):
            h, stacked = carry
            lp, li, per_slot = xs
            caches = {**stacked, **per_slot}
            for k, (mx, ff) in zip(keys, subs):
                h, caches[k] = layer_decode(
                    lp[k] if len(subs) > 1 else lp, cfg, h, position,
                    caches[k], li, mixer=mx, ffn=ff, fmt=fmt, impl=impl,
                    interpret=interpret, mrope_positions=mrope_pos,
                    block_tables=block_tables, lengths=lengths,
                    paged_impl=paged_impl)
            return ((h, {k: caches[k] for k in stacked}),
                    {k: caches[k] for k in per_slot})
        with jax.named_scope("layers"):     # the body is traced inside
            (h, stacked), per_slot = jax.lax.scan(
                body, (h, stacked),
                (params[name], jnp.arange(count, dtype=jnp.int32), per_slot),
                unroll=flags.inner_unroll())
        group = {**stacked, **per_slot}
        new_caches[name] = group if len(subs) > 1 else group["sub0"]
    with jax.named_scope("lm_head"):
        h = layers.rmsnorm_apply(params["final_norm"], h, cfg.norm_eps)
        logits = _lm_head(params, cfg, h, quant, impl, interpret)
    return logits, new_caches


def lm_cache_shapes(cfg: ModelConfig, batch: int, seq: int) -> Dict:
    """Abstract cache pytree (shapes only) for pre-allocated decode."""
    out = {}
    for name, count, subs in layer_groups(cfg):
        if len(subs) == 1:
            shape = layer_cache_shape(cfg, subs[0][0], batch, seq)
            out[name] = {k: (count,) + v for k, v in shape.items()}
        else:
            blk = {}
            for i, (mx, ff) in enumerate(subs):
                shape = layer_cache_shape(cfg, mx, batch, seq)
                blk[f"sub{i}"] = {k: (count,) + v for k, v in shape.items()}
            out[name] = blk
    return out
