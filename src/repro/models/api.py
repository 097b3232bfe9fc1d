"""Unified model API: build_model(cfg) -> ModelAPI.

One object per architecture exposing init / loss / forward / prefill /
decode_step / cache_shapes / input_specs. ``input_specs`` returns
ShapeDtypeStructs (weak-type-correct, shardable, no allocation) — the
dry-run lowers against these.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import encdec, lm


@dataclasses.dataclass(frozen=True)
class ModelAPI:
    cfg: ModelConfig
    init: Callable                    # (key, quant) -> params
    loss: Callable                    # (params, batch, **opts) -> scalar
    forward: Callable                 # (params, batch, **opts) -> (logits, aux)
    prefill: Callable                 # (params, batch, **opts) -> (logits,
                                      # cache) — lockstep/eval entry only;
                                      # the serving runtime never calls it
                                      # (prompts stream through decode_step)
    decode_step: Callable             # (params, token, position, cache, **o)
    cache_shapes: Callable            # (batch, seq) -> shape pytree
    # encdec only: admission-time encoder pass for chunked prefill —
    # (params, frames, **opts) -> cache pytree (cross KV + self stubs).
    encode_cross: Optional[Callable] = None

    # ------------------------------------------------------------------
    def abstract_params(self, quant: str = "none"):
        """Parameter ShapeDtypeStructs without allocating (dry-run path)."""
        return jax.eval_shape(
            functools.partial(self.init, quant=quant), jax.random.PRNGKey(0))

    def cache_specs(self, batch: int, seq: int,
                    dtype=jnp.bfloat16) -> Dict:
        shapes = self.cache_shapes(batch, seq)

        def to_spec(x):
            if isinstance(x, tuple):
                return jax.ShapeDtypeStruct(x, dtype)
            return x
        return jax.tree.map(to_spec, shapes,
                            is_leaf=lambda x: isinstance(x, tuple))

    def paged_cache_shapes(self, num_slots: int, num_physical: int,
                           block_size: int):
        """Paged-arena storage layout: returns ``(shapes, paged)`` pytrees.

        Leaves whose extent follows the sequence length (attention K/V,
        MLA latents) become head-major physical pages ``(count,
        num_physical, heads, block_size, feature)`` with ``paged`` True —
        GQA keeps its kv-head axis, MLA latents get a unit one, so every
        page is a run of ``(block_size, feature)`` tiles the fused kernel
        reads whole. Constant-size leaves (SSM recurrent/conv state,
        enc-dec cross KV) keep per-slot storage ``(count, num_slots,
        ...)`` with ``paged`` False. Detection probes ``cache_shapes`` at
        two sequence lengths and pages exactly the leaves/axes that moved
        — no per-family special-casing."""
        s_a, s_b = 160, 224      # probe lengths; avoid constant-dim collisions
        ta = self.cache_shapes(num_slots, s_a)
        tb = self.cache_shapes(num_slots, s_b)
        is_shape = lambda x: isinstance(x, tuple)

        def pick(sa, sb):
            if sa == sb:
                return sa
            diff = [i for i, (x, y) in enumerate(zip(sa, sb)) if x != y]
            assert diff == [2] and sa[2] == s_a and len(sa) in (4, 5), \
                f"unsupported cache layout for paging: {sa} vs {sb}"
            heads = sa[3:-1] or (1,)
            return (sa[0], num_physical) + heads + (block_size, sa[-1])

        shapes = jax.tree.map(pick, ta, tb, is_leaf=is_shape)
        paged = jax.tree.map(lambda sa, sb: sa != sb, ta, tb,
                             is_leaf=is_shape)
        return shapes, paged

    def paged_decode_specs(self, num_slots: int, num_blocks: int,
                           block_size: int, max_seq: int,
                           dtype=jnp.bfloat16,
                           kv_quant: str = "none") -> Dict:
        """Entry ShapeDtypeStructs for the paged serving decode step:
        ``slot_decode_specs`` plus the per-slot block tables, over
        (num_blocks + 1, block_size) page storage (the +1 is the arena's
        null block). ``kv_quant="int8"`` mirrors the quantized arena
        layout: each paged leaf becomes ``{"q": int8 pages, "s": float16
        scale pages}`` (scale shape = page shape minus the feature
        axis: ``(count, pages, heads, block_size)``), matching
        ``PagedKVArena.page_layout``."""
        shapes, paged = self.paged_cache_shapes(num_slots, num_blocks + 1,
                                                block_size)
        is_shape = lambda x: isinstance(x, tuple)

        def to_spec(x, is_paged):
            if not is_shape(x):
                return x
            if is_paged and kv_quant == "int8":
                return {"q": jax.ShapeDtypeStruct(x, jnp.int8),
                        "s": jax.ShapeDtypeStruct(x[:-1], jnp.float16)}
            return jax.ShapeDtypeStruct(x, dtype)
        max_blocks = -(-max_seq // block_size)
        return {
            "token": jax.ShapeDtypeStruct((num_slots, 1), jnp.int32),
            "positions": jax.ShapeDtypeStruct((num_slots,), jnp.int32),
            "active": jax.ShapeDtypeStruct((num_slots,), jnp.bool_),
            "block_tables": jax.ShapeDtypeStruct((num_slots, max_blocks),
                                                 jnp.int32),
            "cache": jax.tree.map(to_spec, shapes, paged,
                                  is_leaf=is_shape),
        }

    def chunked_step_specs(self, num_slots: int, chunk: int, max_seq: int,
                           dtype=jnp.bfloat16,
                           block_size: Optional[int] = None,
                           num_blocks: Optional[int] = None,
                           kv_quant: str = "none") -> Dict:
        """Entry ShapeDtypeStructs for the *unified* chunked-prefill step:
        ONE traced shape (num_slots, chunk) covers prompt ingestion AND
        generation — per-slot base positions + valid-entry counts (the
        retired bucketed prefill had its own entry point; ``prefill``
        now serves only lockstep/eval callers). Paged mode adds the
        block tables the decode step's ``paged_impl`` (fused kernel or
        gather oracle) reads K/V through; vlm adds the stub
        patch-embedding override. State leaves may be stored in a
        different dtype than requested (see kvcache.step_leaf_dtypes);
        these specs describe the uniform-dtype request."""
        i32 = jnp.int32
        specs = {
            "tokens": jax.ShapeDtypeStruct((num_slots, chunk), i32),
            "positions": jax.ShapeDtypeStruct((num_slots,), i32),
            "lengths": jax.ShapeDtypeStruct((num_slots,), i32),
            "active": jax.ShapeDtypeStruct((num_slots,), jnp.bool_),
        }
        if block_size is not None:
            paged = self.paged_decode_specs(num_slots, num_blocks,
                                            block_size, max_seq, dtype,
                                            kv_quant=kv_quant)
            specs["block_tables"] = paged["block_tables"]
            specs["cache"] = paged["cache"]
        else:
            specs["cache"] = self.cache_specs(num_slots, max_seq, dtype)
        if self.cfg.family == "vlm":
            specs["embeds"] = jax.ShapeDtypeStruct(
                (num_slots, chunk, self.cfg.d_model), dtype)
            specs["embeds_mask"] = jax.ShapeDtypeStruct(
                (num_slots, chunk), jnp.bool_)
        return specs

    def spec_step_specs(self, num_slots: int, chunk: int, max_seq: int,
                        dtype=jnp.bfloat16,
                        block_size: Optional[int] = None,
                        num_blocks: Optional[int] = None) -> Dict:
        """Entry ShapeDtypeStructs for the speculative *verify* step: the
        unified chunked step doubles as the verifier (same model pass,
        same traced (num_slots, chunk) shape), with one extra per-slot
        vector — ``prop_lens``, the number of proposal lanes riding
        behind each slot's committed token (``tokens[:, 0]`` committed,
        ``tokens[:, 1:1+k]`` proposals; row j's logits verify the token
        fed at j + 1). The engine's verification head
        (``sampling.verify_slots``) consumes the full (slots, chunk, V)
        logits, so no new model entry point exists — these specs abstract
        the verify step's entry in the engine's argument order
        (``prop_lens`` follows ``lengths``) and are kept honest by an
        eval_shape lowering test in tests/test_speculative.py."""
        base = self.chunked_step_specs(num_slots, chunk, max_seq, dtype,
                                       block_size=block_size,
                                       num_blocks=num_blocks)
        specs = {}
        for name, spec in base.items():
            specs[name] = spec
            if name == "lengths":
                specs["prop_lens"] = jax.ShapeDtypeStruct((num_slots,),
                                                          jnp.int32)
        return specs

    def slot_decode_specs(self, num_slots: int, max_seq: int,
                          dtype=jnp.bfloat16) -> Dict:
        """Entry ShapeDtypeStructs for the serving engine's slot-batched
        decode step: per-slot token/position/active vectors over a
        (num_slots, max_seq) KV arena. Used for AOT lowering/warmup of the
        continuous-batching step executor."""
        i32 = jnp.int32
        return {
            "token": jax.ShapeDtypeStruct((num_slots, 1), i32),
            "positions": jax.ShapeDtypeStruct((num_slots,), i32),
            "active": jax.ShapeDtypeStruct((num_slots,), jnp.bool_),
            "cache": self.cache_specs(num_slots, max_seq, dtype),
        }

    def input_specs(self, shape: ShapeSpec, dtype=jnp.bfloat16) -> Dict:
        """ShapeDtypeStruct stand-ins for the entry point of this cell."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind == "train":
            specs = {"tokens": jax.ShapeDtypeStruct((b, s), i32),
                     "labels": jax.ShapeDtypeStruct((b, s), i32)}
            specs.update(self._frontend_specs(b, s, dtype))
            return {"batch": specs}
        if shape.kind == "prefill":
            specs = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
            specs.update(self._frontend_specs(b, s, dtype))
            return {"batch": specs}
        # decode: one new token against a KV cache of seq_len.
        return {
            "token": jax.ShapeDtypeStruct((b, 1), i32),
            "position": jax.ShapeDtypeStruct((), i32),
            "cache": self.cache_specs(b, s, dtype),
        }

    def _frontend_specs(self, b: int, s: int, dtype) -> Dict:
        cfg = self.cfg
        out = {}
        if cfg.family == "vlm":
            out["vision_embeds"] = jax.ShapeDtypeStruct(
                (b, min(cfg.vision_tokens, s), cfg.d_model), dtype)
        if cfg.family == "encdec":
            out["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_seq_len, cfg.d_model), dtype)
        return out


def build_model(cfg: ModelConfig) -> ModelAPI:
    if cfg.family == "encdec":
        return ModelAPI(
            cfg=cfg,
            init=lambda key, quant="none": encdec.encdec_init(key, cfg, quant),
            loss=lambda params, batch, **kw: encdec.encdec_loss(
                params, cfg, batch, **_strip(kw)),
            forward=lambda params, batch, **kw: encdec.encdec_forward(
                params, cfg, batch, **_strip(kw)),
            prefill=lambda params, batch, **kw: encdec.encdec_prefill(
                params, cfg, batch, **_drop_remat(_strip(kw))),
            decode_step=lambda params, token, position, cache, **kw:
                encdec.encdec_decode_step(params, cfg, token, position,
                                          cache, **_drop_chunk(
                                              _drop_remat(_strip(kw)))),
            cache_shapes=functools.partial(encdec.encdec_cache_shapes, cfg),
            encode_cross=lambda params, frames, **kw:
                encdec.encdec_encode_cross(params, cfg, frames,
                                           **_drop_remat(_strip(kw))),
        )
    return ModelAPI(
        cfg=cfg,
        init=functools.partial(_lm_init_kw, cfg),
        loss=functools.partial(_lm_loss_kw, cfg),
        forward=functools.partial(_lm_forward_kw, cfg),
        prefill=functools.partial(_lm_prefill_kw, cfg),
        decode_step=functools.partial(_lm_decode_kw, cfg),
        cache_shapes=functools.partial(lm.lm_cache_shapes, cfg),
    )


# functools.partial with positional cfg after key needs small adapters.
def _strip(kw: Dict) -> Dict:
    # encdec functions don't take act_sharding; drop it (whisper is small).
    return {k: v for k, v in kw.items()
            if v is not None and k != "act_sharding"}


def _drop_remat(kw: Dict) -> Dict:
    return {k: v for k, v in kw.items() if k != "remat"}


def _drop_chunk(kw: Dict) -> Dict:
    return {k: v for k, v in kw.items() if k != "kv_chunk"}


def _lm_init_kw(cfg, key, quant="none"):
    return lm.lm_init(key, cfg, quant)


def _lm_loss_kw(cfg, params, batch, **kw):
    return lm.lm_loss(params, cfg, batch, **kw)


def _lm_forward_kw(cfg, params, batch, **kw):
    return lm.lm_forward(params, cfg, batch, **kw)


def _lm_prefill_kw(cfg, params, batch, **kw):
    return lm.lm_prefill(params, cfg, batch, **_drop_remat(kw))


def _lm_decode_kw(cfg, params, token, position, cache, **kw):
    return lm.lm_decode_step(params, cfg, token, position, cache,
                             **_drop_chunk(_drop_remat(kw)))
