"""Plain float32 reference of the published Qwen3 dense block, and the
benchmark's own weights for it.

Qwen3 (huggingface.co/Qwen/Qwen3-1.7B, Qwen3-8B ``config.json``;
``modeling_qwen3.py``): pre-norm decoder layers with RMSNorm (eps from the
config), grouped-query attention with a per-head RMSNorm on q and k before
rotary embedding (rotate-half, base ``rope_theta``), causal softmax at
scale ``head_dim ** -0.5``, a SwiGLU MLP (``down(silu(gate(x)) * up(x))``),
a final RMSNorm and a head that is the embedding (``tie_word_embeddings``)
or a separate matrix. Linear weights are stored ``(out, in)`` as in the
published checkpoints.

Weights are random, drawn from the seed in bfloat16 (the served type;
norm gains in float32), one layer at a time from ``fold_in(key, layer)``,
so the reference can regenerate any layer alone and never holds more than
one in memory. The program receives the same values, packed into its own
parameter tree by ``to_program``. This file imports nothing of the
program.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

EMBED_SALT, HEAD_SALT, FINAL_NORM_SALT = 1 << 20, (1 << 20) + 1, \
    (1 << 20) + 2
# Query rows per attention block in the reference: bounds the f32 score
# tensor to (batch, heads, block, seq).
Q_BLOCK = 512
# float8_e4m3fn's largest finite value: per-row scales map each row's
# absolute maximum onto it.
FP8_MAX = 448.0


def dims(cfg: Dict) -> Dict[str, int]:
    """The sizes the reference needs, from a config file's keys."""
    return {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
            "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"]}


# ----------------------------------------------------------------------
# Weights from the seed
# ----------------------------------------------------------------------
def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def _gain(key, n):
    # Gains near 1 but not 1, so a norm applied with the wrong gain, or
    # not at all, shows in the logits.
    return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)


def layer_weights(key, cfg: Dict, layer) -> Dict[str, jnp.ndarray]:
    """Layer ``layer``'s weights (HF names, ``(out, in)``). Linear
    weights have std ``fan_in ** -0.5`` so every layer moves the residual
    stream by O(1) and the logits depend on the whole context."""
    m = dims(cfg)
    d, hd, ff = m["d"], m["hd"], m["ff"]
    ks = jax.random.split(jax.random.fold_in(key, layer), 11)
    return {
        "input_layernorm": _gain(ks[0], d),
        "q_proj": _normal(ks[1], (m["H"] * hd, d), d ** -0.5),
        "k_proj": _normal(ks[2], (m["Hkv"] * hd, d), d ** -0.5),
        "v_proj": _normal(ks[3], (m["Hkv"] * hd, d), d ** -0.5),
        "o_proj": _normal(ks[4], (d, m["H"] * hd), (m["H"] * hd) ** -0.5),
        "q_norm": _gain(ks[5], hd),
        "k_norm": _gain(ks[6], hd),
        "post_attention_layernorm": _gain(ks[7], d),
        "gate_proj": _normal(ks[8], (ff, d), d ** -0.5),
        "up_proj": _normal(ks[9], (ff, d), d ** -0.5),
        "down_proj": _normal(ks[10], (d, ff), ff ** -0.5),
    }


def embed_weights(key, cfg: Dict) -> jnp.ndarray:
    """``embed_tokens`` (V, d), std 0.02 (the published initializer)."""
    m = dims(cfg)
    return _normal(jax.random.fold_in(key, EMBED_SALT), (m["V"], m["d"]),
                   0.02)


def head_weights(key, cfg: Dict) -> jnp.ndarray:
    """The untied ``lm_head`` (V, d), std ``d ** -0.5``."""
    m = dims(cfg)
    return _normal(jax.random.fold_in(key, HEAD_SALT), (m["V"], m["d"]),
                   m["d"] ** -0.5)


def final_norm_weights(key, cfg: Dict) -> jnp.ndarray:
    """The final RMSNorm gain (d,)."""
    return _gain(jax.random.fold_in(key, FINAL_NORM_SALT), dims(cfg)["d"])


def to_program(key, cfg: Dict) -> Dict:
    """All weights, packed into the serving program's parameter tree:
    layers stacked on a leading axis under ``layers0``, linear weights as
    ``{"w": (out, in)}``, norm gains as ``{"g": ...}``. Traced inside the
    one jitted call that makes the served weights."""
    stack = jax.vmap(lambda i: layer_weights(key, cfg, i))(
        jnp.arange(dims(cfg)["L"]))
    lin = lambda n: {"w": stack[n]}
    gain = lambda n: {"g": stack[n]}
    params = {
        "embed": {"w": embed_weights(key, cfg)},
        "final_norm": {"g": final_norm_weights(key, cfg)},
        "layers0": {
            "mixer_norm": gain("input_layernorm"),
            "attn": {"q": lin("q_proj"), "k": lin("k_proj"),
                     "v": lin("v_proj"), "o": lin("o_proj"),
                     "q_norm": gain("q_norm"), "k_norm": gain("k_norm")},
            "ffn_norm": gain("post_attention_layernorm"),
            "ffn": {"gate": lin("gate_proj"), "up": lin("up_proj"),
                    "down": lin("down_proj")},
        },
    }
    if not cfg["tie_word_embeddings"]:
        params["lm_head"] = {"w": head_weights(key, cfg)}
    return params


def program_config_fields(cfg: Dict) -> Dict:
    """The program's model-config fields for this configuration."""
    m = dims(cfg)
    return dict(family="dense", num_layers=m["L"], d_model=m["d"],
                num_heads=m["H"], num_kv_heads=m["Hkv"], d_ff=m["ff"],
                vocab_size=m["V"], head_dim=m["hd"], qk_norm=True,
                tie_embeddings=bool(cfg["tie_word_embeddings"]),
                rope_theta=float(cfg["rope_theta"]),
                norm_eps=float(cfg["rms_norm_eps"]), subquadratic=False)


# ----------------------------------------------------------------------
# Reference forward pass (float32), layer by layer
# ----------------------------------------------------------------------
def _fp8(x, axis=-1):
    """Round ``x`` to float8_e4m3fn with one scale per row along
    ``axis`` (absmax onto the format's largest value); returns the
    rounded values back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    """``x @ w.T`` for an ``(out, in)`` weight, in float32; with ``fp8``
    both operands are first rounded to float8 (per token, per output
    channel) — the lower-precision control."""
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum("...k,nk->...n", x, w)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """Rotate-half RoPE over (B, S, H, D) at positions 0..S-1."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, fp8: bool):
    """Causal GQA over (B, S, H|Hkv, D), in query blocks of ``Q_BLOCK``."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    if fp8:
        q, k, v = _fp8(q), _fp8(k), _fp8(v, axis=1)
    outs = []
    for start in range(0, s, Q_BLOCK):
        qb = q[:, start:start + Q_BLOCK]
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        qpos = start + jnp.arange(qb.shape[1])[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= qpos, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if fp8:
            p = _fp8(p)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v))
    return jnp.concatenate(outs, axis=1)


def embed(key, cfg: Dict, tokens):
    """Token embeddings (B, S, d) in float32."""
    return embed_weights(key, cfg)[tokens].astype(jnp.float32)


def layer(key, cfg: Dict, i, h, fp8: bool = False):
    """Decoder layer ``i`` over hidden states (B, S, d), float32."""
    m = dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     layer_weights(key, cfg, i))
    b, s, _ = h.shape
    x = _rms(h, w["input_layernorm"], eps)
    q = _mm(x, w["q_proj"], fp8).reshape(b, s, m["H"], m["hd"])
    k = _mm(x, w["k_proj"], fp8).reshape(b, s, m["Hkv"], m["hd"])
    v = _mm(x, w["v_proj"], fp8).reshape(b, s, m["Hkv"], m["hd"])
    q = _rope(_rms(q, w["q_norm"], eps), float(cfg["rope_theta"]))
    k = _rope(_rms(k, w["k_norm"], eps), float(cfg["rope_theta"]))
    o = _attention(q, k, v, fp8).reshape(b, s, m["H"] * m["hd"])
    h = h + _mm(o, w["o_proj"], fp8)
    x = _rms(h, w["post_attention_layernorm"], eps)
    y = jax.nn.silu(_mm(x, w["gate_proj"], fp8)) * _mm(x, w["up_proj"], fp8)
    return h + _mm(y, w["down_proj"], fp8)


def logits(key, cfg: Dict, h, fp8: bool = False):
    """Final norm and head over hidden rows (..., d) -> (..., V)."""
    x = _rms(h, final_norm_weights(key, cfg), float(cfg["rms_norm_eps"]))
    head = embed_weights(key, cfg) if cfg["tie_word_embeddings"] \
        else head_weights(key, cfg)
    return _mm(x, head, fp8)
