"""Fused paged-attention kernel differential suite (the PR's tentpole).

The fused kernel (``kernels/paged_attention.py``) and the ``paged_view``
dense-gather oracle compute the same attention through different but
mathematically equal softmax factorizations (blocked *online* softmax
with running max/sum vs one dense softmax over the gathered view), so
fp32 layer differentials are pinned to a few-ULP tolerance rather than
bitwise — while everything that CAN be bitwise is asserted bitwise: the
page contents after the shared insert path, the dead-page independence
property, and the engine-level greedy token streams (fused ≡ ref ≡
contiguous, token for token; the pinned seeds are free of the logit
near-ties that could flip a greedy argmax across equal-math
factorizations, the same situation PR 3 documented for chunked vs
bucketed prefill).

Coverage: GQA and MLA × chunk widths {1, 4, block_size+1} ×
fragmented/permuted/partially-null block tables; hypothesis fuzz over
(block_size, chunk, positions, table permutation); dead-page
independence (the O(arena) -> O(live-token) claim in falsifiable form:
garbage written past every slot's live depth cannot change one output
bit); and fused ≡ ref engine e2e on bf16 and fp32 arenas.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ASSIGNED
from repro.kernels.paged_attention import paged_decode_attention
from repro.models import attention as attn
from repro.models.api import build_model
from repro.runtime.engine import ServingEngine
from repro.runtime.request import Request

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAVE_HYPOTHESIS = False

# Blocked-online vs dense softmax at fp32: same math, different
# reduction/rescale order — a few ULPs, never more.
FP32_TOL = dict(atol=2e-6, rtol=2e-5)


@pytest.fixture(scope="module")
def gqa_model():
    cfg = ASSIGNED["qwen3-0.6b"].reduced()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mla_model():
    cfg = ASSIGNED["deepseek-v3-671b"].reduced()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(1))


def _tables(rng, b, mb, nb, null_block, owned=None):
    """Fragmented/permuted tables: each slot's logical blocks map to a
    random disjoint subset of the physical pages, in shuffled physical
    order; entries past ``owned[i]`` blocks hold the null sentinel."""
    perm = rng.permutation(nb)
    t = np.full((b, mb), null_block, np.int32)
    for i in range(b):
        k = mb if owned is None else owned[i]
        t[i, :k] = perm[i * mb:i * mb + k]
    return t


def _to_pages(contig, tables, bs, num_pages):
    """(B, S, H, ...) -> a one-layer stack of head-major (1, num_pages,
    H, bs, ...) pages per a block table (null page left zero)."""
    shape = contig.shape
    pages = np.zeros((num_pages, shape[2], bs) + shape[3:],
                     np.asarray(contig).dtype)
    for i in range(tables.shape[0]):
        for j in range(tables.shape[1]):
            if tables[i, j] == num_pages - 1:
                continue
            pages[tables[i, j]] = np.swapaxes(
                np.asarray(contig[i, j * bs:(j + 1) * bs]), 0, 1)
    return jnp.asarray(pages[None])


def _ref_gqa(q, k_pages, v_pages, tables, pos0, sm):
    kc = attn.paged_view(k_pages, tables)
    vc = attn.paged_view(v_pages, tables)
    pos_mat = attn.decode_positions(pos0, q.shape[0], q.shape[1])
    return attn.decode_attention(q, kc, vc, sm_scale=sm,
                                 kv_len=pos_mat + 1)


# ----------------------------------------------------------------------
# Direct kernel vs gather oracle: GQA layout
# ----------------------------------------------------------------------
@pytest.mark.parametrize("chunk", [1, 4, 5])     # 5 == block_size + 1
def test_kernel_matches_gather_ref_gqa(chunk):
    B, H, Hkv, D, bs, mb = 3, 8, 2, 16, 4, 6
    nb = B * mb
    rng = np.random.RandomState(chunk)
    owned = [mb, 3, 2]                           # partially-null rows
    tables = _tables(rng, B, mb, nb, null_block=nb, owned=owned)
    kc = rng.randn(B, mb * bs, Hkv, D).astype(np.float32)
    vc = rng.randn(B, mb * bs, Hkv, D).astype(np.float32)
    k_pages = _to_pages(kc, tables, bs, nb + 1)
    v_pages = _to_pages(vc, tables, bs, nb + 1)
    q = jnp.asarray(rng.randn(B, chunk, H, D).astype(np.float32))
    # each row's queries stay within its owned blocks
    pos0 = jnp.asarray([max(o * bs - chunk, 0) for o in owned], jnp.int32)
    sm = D ** -0.5

    out = paged_decode_attention(q, k_pages, v_pages, jnp.asarray(tables),
                                 pos0, sm_scale=sm, interpret=True)
    ref = _ref_gqa(q, k_pages, v_pages, jnp.asarray(tables), pos0, sm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **FP32_TOL)


# ----------------------------------------------------------------------
# Direct kernel vs gather oracle: MLA absorbed layout (q2/k2 rope side)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("chunk", [1, 4, 5])
def test_kernel_matches_gather_ref_mla(chunk):
    B, H, rank, rope, bs, mb = 2, 4, 16, 8, 4, 5
    nb = B * mb
    rng = np.random.RandomState(100 + chunk)
    owned = [mb, 2]
    tables = _tables(rng, B, mb, nb, null_block=nb, owned=owned)
    ckv_c = rng.randn(B, mb * bs, 1, rank).astype(np.float32)
    kr_c = rng.randn(B, mb * bs, 1, rope).astype(np.float32)
    ckv = _to_pages(ckv_c, tables, bs, nb + 1)
    krope = _to_pages(kr_c, tables, bs, nb + 1)
    q1 = jnp.asarray(rng.randn(B, chunk, H, rank).astype(np.float32))
    q2 = jnp.asarray(rng.randn(B, chunk, H, rope).astype(np.float32))
    pos0 = jnp.asarray([max(o * bs - chunk, 0) for o in owned], jnp.int32)
    sm = (rank + rope) ** -0.5

    out = paged_decode_attention(q1, ckv, ckv, jnp.asarray(tables), pos0,
                                 sm_scale=sm, q2=q2, k2_pages=krope,
                                 out_dtype=jnp.float32, interpret=True)
    # dense oracle with the decoupled-rope score sum
    tb = jnp.asarray(tables)
    ckv_v = attn.paged_view(ckv, tb)[:, :, 0]        # (B, S, rank)
    kr_v = attn.paged_view(krope, tb)[:, :, 0]       # (B, S, rope)
    s = (jnp.einsum("bqhr,bsr->bhqs", q1, ckv_v)
         + jnp.einsum("bqhe,bse->bhqs", q2, kr_v)) * sm
    pos_mat = attn.decode_positions(pos0, B, chunk)
    mask = jnp.arange(ckv_v.shape[1])[None, None, None, :] \
        < (pos_mat + 1)[:, None, :, None]
    s = jnp.where(mask, s, attn.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bhqs,bsr->bqhr", p, ckv_v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **FP32_TOL)


# ----------------------------------------------------------------------
# Stacked arena: the kernel reads layer ``layer`` of (L, NP, ...) in place
# ----------------------------------------------------------------------
def _stacked(rng, tables, shape, bs, num_pages, layers, kv):
    """``layers`` layers of random pages (float32 values in a bf16 arena,
    or int8 codes with their scale pages): a dict {"q"[, "s"]} of stacked
    (L, num_pages, H, bs, ...) leaves."""
    per_layer = []
    for _ in range(layers):
        x = jnp.asarray(rng.randn(*shape).astype(np.float32))
        parts = dict(zip("qs", attn.quantize_kv(x))) if kv == "int8" \
            else {"q": x.astype(jnp.bfloat16)}
        per_layer.append({k: _to_pages(v, tables, bs, num_pages)
                          for k, v in parts.items()})
    return {k: jnp.concatenate([p[k] for p in per_layer])
            for k in per_layer[0]}


def _layer_view(leaf, tables, layer):
    """f32 dense gather of one layer of a stacked leaf (the oracle)."""
    if "s" in leaf:
        return attn._paged_view_dequant(leaf, tables, layer)
    return attn.paged_view(leaf["q"], tables, layer).astype(jnp.float32)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("kind", ["gqa", "mla"])
def test_kernel_reads_stacked_layer(kind, kv, layer):
    """On a 3-layer arena the kernel at ``layer`` is the per-layer kernel
    on that layer's pages, bit for bit, and matches the dense gather of
    that layer; the other layers hold different values, so a wrong layer
    index cannot pass."""
    B, H, bs, mb, chunk = 2, 4, 4, 5, 3
    hkv, d, d2 = (2, 16, None) if kind == "gqa" else (1, 16, 8)
    nb = B * mb
    rng = np.random.RandomState(31 + layer)
    owned = [mb, 3]
    tables = _tables(rng, B, mb, nb, null_block=nb, owned=owned)
    tb = jnp.asarray(tables)
    mk = lambda dim: _stacked(rng, tables, (B, mb * bs, hkv, dim), bs,
                              nb + 1, 3, kv)
    k, v = mk(d), (mk(d) if kind == "gqa" else None)
    k2 = mk(d2) if kind == "mla" else None
    q = jnp.asarray(rng.randn(B, chunk, H, d).astype(np.float32))
    q2 = jnp.asarray(rng.randn(B, chunk, H, d2).astype(np.float32)) \
        if kind == "mla" else None
    pos0 = jnp.asarray([max(o * bs - chunk, 0) for o in owned], jnp.int32)
    sm = d ** -0.5

    def run(lyr, pick):
        ops = {"k_pages": pick(k["q"]),
               "v_pages": None if v is None else pick(v["q"])}
        if kind == "mla":
            ops.update(q2=q2, k2_pages=pick(k2["q"]))
        if kv == "int8":
            ops.update(k_scales=pick(k["s"]))
            if v is not None:
                ops.update(v_scales=pick(v["s"]))
            if k2 is not None:
                ops.update(k2_scales=pick(k2["s"]))
        return np.asarray(paged_decode_attention(
            q, block_tables=tb, positions=pos0, sm_scale=sm, layer=lyr,
            out_dtype=jnp.float32, interpret=True, **ops))

    out = run(layer, lambda x: x)
    np.testing.assert_array_equal(out, run(0, lambda x: x[layer][None]))
    kc = _layer_view(k, tb, layer)
    if kind == "gqa":
        pos_mat = attn.decode_positions(pos0, B, chunk)
        ref = attn.decode_attention(q, kc, _layer_view(v, tb, layer),
                                    sm_scale=sm, kv_len=pos_mat + 1)
    else:
        ckv, kr = kc[:, :, 0], _layer_view(k2, tb, layer)[:, :, 0]
        s = (jnp.einsum("bqhr,bsr->bhqs", q, ckv)
             + jnp.einsum("bqhe,bse->bhqs", q2, kr)) * sm
        pos_mat = attn.decode_positions(pos0, B, chunk)
        mask = jnp.arange(ckv.shape[1])[None, None, None, :] \
            < (pos_mat + 1)[:, None, :, None]
        p = jax.nn.softmax(jnp.where(mask, s, attn.NEG_INF), axis=-1)
        ref = jnp.einsum("bhqs,bsr->bqhr", p, ckv)
    np.testing.assert_allclose(out, np.asarray(ref), **FP32_TOL)


# ----------------------------------------------------------------------
# Layer-level: gqa_decode / mla_decode fused vs ref impl
# ----------------------------------------------------------------------
@pytest.mark.parametrize("chunk", [1, 4, 5])
def test_gqa_decode_fused_vs_ref_layer(gqa_model, chunk):
    cfg, _, _ = gqa_model
    key = jax.random.PRNGKey(0)
    p = attn.gqa_init(key, cfg)
    B, bs, mb = 3, 4, 6
    nb = B * mb
    hd, hkv = cfg.resolved_head_dim(), cfg.num_kv_heads
    rng = np.random.RandomState(7)
    tables = jnp.asarray(_tables(rng, B, mb, nb, null_block=nb))
    k1, k2, k3 = jax.random.split(key, 3)
    cache = {"k": jax.random.normal(k1, (1, nb + 1, hkv, bs, hd),
                                    jnp.float32),
             "v": jax.random.normal(k2, (1, nb + 1, hkv, bs, hd),
                                    jnp.float32)}
    x = jax.random.normal(k3, (B, chunk, cfg.d_model), jnp.float32)
    pos0 = jnp.asarray([5, 9, 2], jnp.int32)
    lengths = jnp.asarray([chunk, max(chunk - 2, 1), chunk], jnp.int32)

    out_f, cache_f = attn.gqa_decode(p, cfg, x, pos0, cache,
                                     block_tables=tables, lengths=lengths)
    out_r, cache_r = attn.gqa_decode(p, cfg, x, pos0, cache,
                                     block_tables=tables, lengths=lengths,
                                     paged_impl="ref")
    # the insert path is shared: pages must be BIT-identical
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache_f[leaf]),
                                      np.asarray(cache_r[leaf]))
    # valid rows match to fp32 few-ULP tolerance (invalid tails are
    # garbage-by-contract on both impls)
    for b in range(B):
        n = int(lengths[b])
        np.testing.assert_allclose(np.asarray(out_f[b, :n]),
                                   np.asarray(out_r[b, :n]), **FP32_TOL)


@pytest.mark.parametrize("chunk", [1, 3, 5])
def test_mla_decode_fused_vs_ref_layer(mla_model, chunk):
    cfg, _, _ = mla_model
    m = cfg.mla
    key = jax.random.PRNGKey(1)
    p = attn.mla_init(key, cfg)
    B, bs, mb = 2, 4, 6
    nb = B * mb
    rng = np.random.RandomState(8)
    tables = jnp.asarray(_tables(rng, B, mb, nb, null_block=nb))
    k1, k2, k3 = jax.random.split(key, 3)
    cache = {"ckv": jax.random.normal(k1,
                                      (1, nb + 1, 1, bs, m.kv_lora_rank),
                                      jnp.float32),
             "krope": jax.random.normal(k2,
                                        (1, nb + 1, 1, bs,
                                         m.qk_rope_head_dim),
                                        jnp.float32)}
    x = jax.random.normal(k3, (B, chunk, cfg.d_model), jnp.float32)
    pos0 = jnp.asarray([7, 3], jnp.int32)
    lengths = jnp.asarray([chunk, max(chunk - 1, 1)], jnp.int32)

    out_f, cache_f = attn.mla_decode(p, cfg, x, pos0, cache,
                                     block_tables=tables, lengths=lengths)
    out_r, cache_r = attn.mla_decode(p, cfg, x, pos0, cache,
                                     block_tables=tables, lengths=lengths,
                                     paged_impl="ref")
    for leaf in ("ckv", "krope"):
        np.testing.assert_array_equal(np.asarray(cache_f[leaf]),
                                      np.asarray(cache_r[leaf]))
    for b in range(B):
        n = int(lengths[b])
        np.testing.assert_allclose(np.asarray(out_f[b, :n]),
                                   np.asarray(out_r[b, :n]),
                                   atol=5e-6, rtol=5e-5)


# ----------------------------------------------------------------------
# Dead-page independence: the O(arena) -> O(live) claim, falsifiably
# ----------------------------------------------------------------------
def test_dead_pages_cannot_affect_output():
    """Garbage written to every page past a slot's live depth (and to the
    null page) must not change one bit of the fused output — the kernel
    provably reads only live blocks. The gather oracle also masks them,
    but only after materializing the O(arena) view."""
    B, H, Hkv, D, bs, mb, chunk = 2, 4, 2, 8, 4, 8, 3
    nb = B * mb
    rng = np.random.RandomState(11)
    owned = [3, 2]                     # live blocks per slot
    tables = _tables(rng, B, mb, nb, null_block=nb, owned=owned)
    kc = rng.randn(B, mb * bs, Hkv, D).astype(np.float32)
    vc = rng.randn(B, mb * bs, Hkv, D).astype(np.float32)
    k_pages = np.asarray(_to_pages(kc, tables, bs, nb + 1))
    v_pages = np.asarray(_to_pages(vc, tables, bs, nb + 1))
    pos0 = jnp.asarray([max(o * bs - chunk, 0) for o in owned], jnp.int32)
    sm = D ** -0.5
    run = lambda kp, vp: np.asarray(paged_decode_attention(
        jnp.asarray(rng0_q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), pos0, sm_scale=sm, interpret=True))
    rng0_q = rng.randn(B, chunk, H, D).astype(np.float32)
    base = run(k_pages, v_pages)

    live = {int(p) for i in range(B) for p in tables[i, :owned[i]]}
    dead = [p for p in range(nb + 1) if p not in live]
    k_trash, v_trash = k_pages.copy(), v_pages.copy()
    k_trash[:, dead] = 1e9             # huge finite garbage
    v_trash[:, dead] = -1e9
    trashed = run(k_trash, v_trash)
    np.testing.assert_array_equal(base, trashed)


# ----------------------------------------------------------------------
# Hypothesis fuzz: (block_size, chunk, positions, permutation)
# ----------------------------------------------------------------------
if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 8), st.sampled_from([1, 2, 5]),
           st.integers(0, 10 ** 6))
    def test_fuzz_kernel_vs_gather_ref(block_size, chunk, seed):
        """Any (block size, chunk width, per-slot depth, fragmented
        permutation): fused ≡ gather-ref within fp32 ULP tolerance."""
        rng = np.random.RandomState(seed)
        B, H, Hkv, D = 2, 4, 2, 8
        mb = int(rng.randint(1, 5))
        S = mb * block_size
        if S < chunk:                  # need room for the whole chunk
            mb = -(-chunk // block_size)
            S = mb * block_size
        nb = B * mb
        tables = _tables(rng, B, mb, nb, null_block=nb)
        kc = rng.randn(B, S, Hkv, D).astype(np.float32)
        vc = rng.randn(B, S, Hkv, D).astype(np.float32)
        k_pages = _to_pages(kc, tables, block_size, nb + 1)
        v_pages = _to_pages(vc, tables, block_size, nb + 1)
        q = jnp.asarray(rng.randn(B, chunk, H, D).astype(np.float32))
        pos0 = jnp.asarray(rng.randint(0, S - chunk + 1, size=B),
                           jnp.int32)
        sm = D ** -0.5
        out = paged_decode_attention(q, k_pages, v_pages,
                                     jnp.asarray(tables), pos0,
                                     sm_scale=sm, interpret=True)
        ref = _ref_gqa(q, k_pages, v_pages, jnp.asarray(tables), pos0, sm)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   **FP32_TOL)


# ----------------------------------------------------------------------
# Engine e2e: fused ≡ ref token-for-token (the serve-level flag)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["qwen3-0.6b", "deepseek-v3-671b"])
def test_engine_fused_matches_ref_e2e(arch, gqa_model, mla_model):
    """The same greedy stream through ``paged_attn="fused"`` and
    ``paged_attn="ref"`` engines emits identical tokens (bf16 arena and
    fp32 arena), with one traced step each — prefill chunks, mid-decode
    block growth and slot turnover all ride the fused kernel."""
    cfg, model, params = gqa_model if arch == "qwen3-0.6b" else mla_model
    rng = np.random.RandomState(13)
    reqs = [Request(rid=i, tokens=rng.randint(0, cfg.vocab_size,
                                              int(rng.randint(4, 12))),
                    max_new_tokens=4) for i in range(5)]
    clone = lambda: [Request(rid=r.rid, tokens=r.tokens.copy(),
                             max_new_tokens=4) for r in reqs]
    for dtype in (jnp.bfloat16, jnp.float32):
        fused = ServingEngine(model, params, num_slots=2, max_seq=24,
                              chunk_size=4, block_size=4,
                              cache_dtype=dtype)
        ref = ServingEngine(model, params, num_slots=2, max_seq=24,
                            chunk_size=4, block_size=4, paged_attn="ref",
                            cache_dtype=dtype)
        rf = fused.serve(clone(), seed=0, realtime=False)
        rr = ref.serve(clone(), seed=0, realtime=False)
        assert rf.step_compiles <= 1 and rr.step_compiles <= 1
        for a, b in zip(rf.sequences, rr.sequences):
            assert a.rid == b.rid
            assert a.generated == b.generated, \
                f"{arch}/{dtype.__name__}: request {a.rid} diverged " \
                f"fused vs ref: {a.generated} vs {b.generated}"


def test_engine_rejects_unknown_paged_attn(gqa_model):
    cfg, model, params = gqa_model
    with pytest.raises(ValueError):
        ServingEngine(model, params, num_slots=1, max_seq=16,
                      block_size=4, paged_attn="nope")
