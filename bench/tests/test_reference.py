"""The plain float32 Qwen3 reference against the program at CPU size, the
benchmark's weights against the program's parameter tree, and the
FLOP/byte counters against values worked out by hand."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counters, spec
from bench.tests import small

QWEN3 = spec.load_module(spec.BENCH_DIR / "configs" / "qwen3.py")


def config(name):
    return json.loads((spec.BENCH_DIR / "configs" / f"{name}.json")
                      .read_text())


def small_config(tied=True):
    cfg = config("qwen3-1.7b")
    cfg.update(small.SMALL, tie_word_embeddings=tied)
    return cfg


def program_model(cfg):
    from repro.configs.base import ModelConfig
    from repro.models.api import build_model
    return build_model(ModelConfig(name="t", **QWEN3.program_config_fields(
        cfg)))


CONFIGS = sorted(p.stem for p in (spec.BENCH_DIR / "configs").glob("*.json"))


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_fit_the_program_tree_at_published_widths(name):
    cfg = config(name)
    model = program_model(cfg)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: QWEN3.to_program(k, cfg),
                         jax.random.key(0))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def reference_logits(cfg, key, tokens):
    with jax.default_matmul_precision("highest"):
        h = QWEN3.embed(key, cfg, tokens)
        for i in range(cfg["num_hidden_layers"]):
            h = QWEN3.layer(key, cfg, i, h)
        return np.asarray(QWEN3.logits(key, cfg, h))


@pytest.mark.parametrize("tied", [True, False])
def test_reference_matches_program_prefill_and_decode(tied):
    """The program's chunked prefill (4-token chunks into its cache) and
    then one-token decode steps give, at every position, the logits of
    the reference's full forward pass, to bfloat16 serving error: the
    program computes in bfloat16, the reference in float32 on the same
    (bfloat16) weights."""
    cfg = small_config(tied)
    model = program_model(cfg)
    key = jax.random.key(5)
    params = jax.jit(lambda k: QWEN3.to_program(k, cfg))(key)
    seq = np.random.default_rng(0).integers(0, cfg["vocab_size"], 22)
    ref = reference_logits(cfg, key, jnp.asarray(seq[None]))[0]
    cache = jax.tree.map(lambda s: jnp.zeros(s, jnp.bfloat16),
                         model.cache_shapes(1, 32),
                         is_leaf=lambda x: isinstance(x, tuple))
    step = jax.jit(lambda p, t, pos, c, n: model.decode_step(
        p, t, pos, c, lengths=n, interpret=True))
    got, pos = [], 0
    for width in [4, 4, 4, 4, 1, 1, 1, 1, 1, 1]:      # 16 prompt + 6 decode
        toks = np.zeros((1, 4), np.int32)
        toks[0, :width] = seq[pos:pos + width]
        logits, cache = step(params, jnp.asarray(toks),
                             jnp.array([pos], jnp.int32), cache,
                             jnp.array([width], jnp.int32))
        got.append(np.asarray(logits, np.float32)[0, :width])
        pos += width
    got = np.concatenate(got)
    scale = np.abs(ref).max()
    err = np.abs(got - ref).max()
    assert err < 0.03 * scale, (err, scale)
    # The comparison is not vacuous: a cache read off by one position
    # (the reference shifted) misses by far more.
    assert np.abs(ref[1:] - ref[:-1]).max() > 10 * err


def test_fp8_control_departs_from_reference():
    cfg = small_config()
    key = jax.random.key(1)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, (1, 16)))
    with jax.default_matmul_precision("highest"):
        h32 = QWEN3.embed(key, cfg, toks)
        h8 = h32
        for i in range(cfg["num_hidden_layers"]):
            h32 = QWEN3.layer(key, cfg, i, h32)
            h8 = QWEN3.layer(key, cfg, i, h8, fp8=True)
        z32 = np.asarray(QWEN3.logits(key, cfg, h32))
        z8 = np.asarray(QWEN3.logits(key, cfg, h8, fp8=True))
    rel = np.abs(z8 - z32).max() / np.abs(z32).max()
    assert 0.005 < rel < 0.5


M = {"L": 2, "d": 8, "H": 4, "Hkv": 2, "hd": 2, "ff": 16, "V": 100}


def test_linear_params_by_hand():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8 -> 192; mlp 3 x 8 x 16 = 384
    assert counters.linear_params(M) == 2 * (192 + 384)


def test_model_flops_one_step_by_hand():
    # 3 tokens after 5 cached, one sampled:
    # linear 2 * 1152 * 3 = 6912; pairs 6 + 7 + 8 = 21;
    # attention 4 * L2 * H4 * hd2 * 21 = 1344; head 2 * 8 * 100 = 1600.
    assert counters.causal_pairs(5, 3) == 21
    assert counters.model_flops(M, 5, 3, 1) == 6912 + 1344 + 1600


def test_paged_attention_work_one_step_by_hand():
    # One decode token after 9 cached: pairs 10; flops 4*2*4*2*10 = 640.
    # Bytes per layer: K and V of 10 positions x 2 heads x 2 x 2 B = 160,
    # query and output 2 x 1 x 4 x 2 x 2 B = 32; two layers: 384.
    assert counters.paged_attention_work(M, 9, 1) == (640, 384)
    assert counters.roofline_seconds(640, 384, 100.0, 1.0) == 384.0
