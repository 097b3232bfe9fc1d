"""Quantized KV pages (ISSUE 8): differential suite.

Layers of proof, weakest assumption first:

  * ``quantize_kv`` round-trip: per-element error bounded by half a
    quantization step; all-zero rows map to (codes 0, scale 0) and back
    to exactly zero — the bit that makes every arena zeroing contract
    representation-agnostic.
  * fused kernel with int8 pages + scale pages vs the dense
    dequant-gather oracle, over fragmented/permuted/partially-null
    tables (GQA direct; GQA and MLA again at layer level through
    ``gqa_decode``/``mla_decode``, where insert bit-identity between
    the fused and ref paths is also asserted).
  * engine e2e: two int8 engines (fused vs gather-ref) emit identical
    greedy token streams; the int8 engine agrees with the bf16 engine
    except at documented near-tie flips (the bench gates the
    margin-confident rate at >= 0.99).
  * arena contracts on the quantized layout: rollback bit-identity
    (hypothesis fuzz over block_size x positions), prefix-sharing CoW
    splits copying codes AND scales bit-for-bit, ``write_prefill``
    refusal.
  * fail-fast surfaces: ``ServingEngine`` constructor and
    ``launch/serve.py``'s ``validate_args`` refuse every incompatible
    combination with a rationale, one test per refusal.

See docs/kernel-contracts.md for the written layout contract.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ASSIGNED
from repro.kernels.paged_attention import paged_decode_attention
from repro.launch import serve
from repro.models import attention as attn
from repro.models.api import build_model
from repro.runtime.engine import ServingEngine
from repro.runtime.kvcache import KVArena, PagedKVArena
from repro.runtime.request import Request

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAVE_HYPOTHESIS = False

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


# ----------------------------------------------------------------------
# quantize_kv / dequantize_kv: the representation itself
# ----------------------------------------------------------------------
def test_quantize_roundtrip_error_bound():
    """Per-element |x - deq(q(x))| <= scale/2 (absmax rounding), and the
    max-magnitude element of every row survives exactly at |code| 127."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(5, 7, 16).astype(np.float32)) * 3.0
    q, s = attn.quantize_kv(x)
    assert q.dtype == jnp.int8 and s.dtype == attn.KV_QUANT_SCALE_DTYPE
    assert q.shape == x.shape and s.shape == x.shape[:-1]
    err = np.abs(np.asarray(attn.dequantize_kv(q, s)) - np.asarray(x))
    # fp16 scale storage widens the pure-int8 half-step bound: the
    # stored scale differs relatively by up to 2^-11, worth up to
    # 127 * 2^-11 ~ 0.062 scale units at the largest code
    bound = np.asarray(s, np.float32)[..., None] * 0.57 + 1e-6
    assert (err <= bound).all()
    assert np.abs(np.asarray(q)).max() == 127


def test_quantize_zero_rows_are_bit_exact():
    """An all-zero row -> (codes 0, scale 0) -> exactly 0.0 on dequant:
    never-written pages, rolled-back positions and the null page stay
    bit-identical to the unquantized arena's zeros."""
    x = jnp.zeros((4, 2, 8), jnp.float32)
    q, s = attn.quantize_kv(x)
    assert not np.asarray(q).any() and not np.asarray(s).any()
    out = np.asarray(attn.dequantize_kv(q, s))
    assert (out == 0.0).all() and not np.signbit(out).any()


# ----------------------------------------------------------------------
# Fused kernel vs dense dequant-gather oracle (direct, GQA layout)
# ----------------------------------------------------------------------
def _tables(rng, b, mb, nb, null_block, owned=None):
    perm = rng.permutation(nb)
    t = np.full((b, mb), null_block, np.int32)
    for i in range(b):
        k = mb if owned is None else owned[i]
        t[i, :k] = perm[i * mb:i * mb + k]
    return t


def _to_pages(contig, tables, bs, num_pages):
    """(B, S, H, ...) -> a one-layer stack of head-major (1, num_pages,
    H, bs, ...) pages."""
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


@pytest.mark.parametrize("chunk", [1, 4, 5])
def test_kernel_quantized_matches_dequant_oracle(chunk):
    B, H, Hkv, D, bs, mb = 3, 8, 2, 16, 4, 6
    nb = B * mb
    rng = np.random.RandomState(40 + chunk)
    owned = [mb, 3, 2]
    tables = _tables(rng, B, mb, nb, null_block=nb, owned=owned)
    kq, ks = attn.quantize_kv(jnp.asarray(
        rng.randn(B, mb * bs, Hkv, D).astype(np.float32)))
    vq, vs = attn.quantize_kv(jnp.asarray(
        rng.randn(B, mb * bs, Hkv, D).astype(np.float32)))
    k_pages = _to_pages(kq, tables, bs, nb + 1)
    ks_pages = _to_pages(ks, tables, bs, nb + 1)
    v_pages = _to_pages(vq, tables, bs, nb + 1)
    vs_pages = _to_pages(vs, tables, bs, nb + 1)
    q = jnp.asarray(rng.randn(B, chunk, H, D).astype(np.float32))
    pos0 = jnp.asarray([max(o * bs - chunk, 0) for o in owned], jnp.int32)
    sm = D ** -0.5
    tb = jnp.asarray(tables)

    out = paged_decode_attention(q, k_pages, v_pages, tb, pos0, sm_scale=sm,
                                 k_scales=ks_pages, v_scales=vs_pages,
                                 interpret=True)
    kc = attn._paged_view_dequant({"q": k_pages, "s": ks_pages}, tb)
    vc = attn._paged_view_dequant({"q": v_pages, "s": vs_pages}, tb)
    pos_mat = attn.decode_positions(pos0, B, chunk)
    ref = attn.decode_attention(q, kc, vc, sm_scale=sm, kv_len=pos_mat + 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **FP32_TOL)


def test_kernel_requires_matched_scale_operands():
    """The scale operands travel as a set: v_pages without v_scales (or
    k2 without k2_scales) under quantization is a contract violation."""
    rng = np.random.RandomState(5)
    pages = jnp.asarray(rng.randint(-127, 127, (1, 3, 2, 1, 8)), jnp.int8)
    scales = jnp.ones((1, 3, 2, 1), jnp.float16)
    tb = jnp.asarray([[0, 1]], jnp.int32)
    q = jnp.asarray(rng.randn(1, 1, 2, 8).astype(np.float32))
    with pytest.raises(AssertionError):
        paged_decode_attention(q, pages, pages, tb, jnp.asarray([0]),
                               sm_scale=1.0, k_scales=scales,
                               interpret=True)


# ----------------------------------------------------------------------
# Layer level: quantized fused vs quantized ref (GQA and MLA)
# ----------------------------------------------------------------------
def _quant_pages(key, shape):
    """Random quantized page set {"q", "s"} with realistic joint stats
    (quantize a dense normal tensor rather than sampling codes/scales
    independently)."""
    q, s = attn.quantize_kv(jax.random.normal(key, shape, jnp.float32))
    return {"q": q, "s": s}


@pytest.mark.parametrize("chunk", [1, 4, 5])
def test_gqa_decode_quantized_fused_vs_ref(gqa_model, chunk):
    cfg, _, _ = gqa_model
    key = jax.random.PRNGKey(2)
    p = attn.gqa_init(key, cfg)
    B, bs, mb = 3, 4, 6
    nb = B * mb
    hd, hkv = cfg.resolved_head_dim(), cfg.num_kv_heads
    rng = np.random.RandomState(17)
    tables = jnp.asarray(_tables(rng, B, mb, nb, null_block=nb))
    k1, k2, k3 = jax.random.split(key, 3)
    cache = {"k": _quant_pages(k1, (1, nb + 1, hkv, bs, hd)),
             "v": _quant_pages(k2, (1, nb + 1, hkv, bs, hd))}
    x = jax.random.normal(k3, (B, chunk, cfg.d_model), jnp.float32)
    pos0 = jnp.asarray([5, 9, 2], jnp.int32)
    lengths = jnp.asarray([chunk, max(chunk - 2, 1), chunk], jnp.int32)

    out_f, cache_f = attn.gqa_decode(p, cfg, x, pos0, cache,
                                     block_tables=tables, lengths=lengths)
    out_r, cache_r = attn.gqa_decode(p, cfg, x, pos0, cache,
                                     block_tables=tables, lengths=lengths,
                                     paged_impl="ref")
    # quantize-on-insert is shared: codes AND scales bit-identical
    for leaf in ("k", "v"):
        for part in ("q", "s"):
            np.testing.assert_array_equal(
                np.asarray(cache_f[leaf][part]),
                np.asarray(cache_r[leaf][part]))
    for b in range(B):
        n = int(lengths[b])
        np.testing.assert_allclose(np.asarray(out_f[b, :n]),
                                   np.asarray(out_r[b, :n]), **FP32_TOL)


@pytest.mark.parametrize("chunk", [1, 3, 5])
def test_mla_decode_quantized_fused_vs_ref(mla_model, chunk):
    cfg, _, _ = mla_model
    m = cfg.mla
    key = jax.random.PRNGKey(3)
    p = attn.mla_init(key, cfg)
    B, bs, mb = 2, 4, 6
    nb = B * mb
    rng = np.random.RandomState(18)
    tables = jnp.asarray(_tables(rng, B, mb, nb, null_block=nb))
    k1, k2, k3 = jax.random.split(key, 3)
    cache = {"ckv": _quant_pages(k1, (1, nb + 1, 1, bs, m.kv_lora_rank)),
             "krope": _quant_pages(k2,
                                   (1, nb + 1, 1, bs, m.qk_rope_head_dim))}
    x = jax.random.normal(k3, (B, chunk, cfg.d_model), jnp.float32)
    pos0 = jnp.asarray([7, 3], jnp.int32)
    lengths = jnp.asarray([chunk, max(chunk - 1, 1)], jnp.int32)

    out_f, cache_f = attn.mla_decode(p, cfg, x, pos0, cache,
                                     block_tables=tables, lengths=lengths)
    out_r, cache_r = attn.mla_decode(p, cfg, x, pos0, cache,
                                     block_tables=tables, lengths=lengths,
                                     paged_impl="ref")
    for leaf in ("ckv", "krope"):
        for part in ("q", "s"):
            np.testing.assert_array_equal(
                np.asarray(cache_f[leaf][part]),
                np.asarray(cache_r[leaf][part]))
    for b in range(B):
        n = int(lengths[b])
        np.testing.assert_allclose(np.asarray(out_f[b, :n]),
                                   np.asarray(out_r[b, :n]),
                                   atol=5e-6, rtol=5e-5)


# ----------------------------------------------------------------------
# Engine e2e: int8 fused == int8 ref token-for-token; vs bf16 agreement
# ----------------------------------------------------------------------
def _serve_tokens(model, params, reqs, **kw):
    eng = ServingEngine(model, params, num_slots=2, max_seq=24,
                        chunk_size=4, block_size=4, **kw)
    rep = eng.serve([Request(rid=r.rid, tokens=r.tokens.copy(),
                             max_new_tokens=r.max_new_tokens)
                     for r in reqs], seed=0, realtime=False)
    assert rep.step_compiles <= 1
    return [s.generated for s in rep.sequences]


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "deepseek-v3-671b"])
def test_engine_int8_fused_matches_ref_e2e(arch, gqa_model, mla_model):
    """Both int8 paths read the SAME quantized representation, so fused
    vs gather-ref must agree token-for-token (the pinned seeds are free
    of argmax near-ties, as in the unquantized differential)."""
    cfg, model, params = gqa_model if arch == "qwen3-0.6b" else mla_model
    rng = np.random.RandomState(21)
    reqs = [Request(rid=i, tokens=rng.randint(0, cfg.vocab_size,
                                              int(rng.randint(4, 12))),
                    max_new_tokens=4) for i in range(5)]
    fused = _serve_tokens(model, params, reqs, kv_quant="int8")
    ref = _serve_tokens(model, params, reqs, kv_quant="int8",
                        paged_attn="ref")
    assert fused == ref


def test_engine_int8_agreement_with_bf16(gqa_model):
    """int8 vs unquantized greedy streams on the same workload: identical
    except at near-tie argmax flips. On a random-init surrogate ties are
    common (see bench_serving part 7's margin analysis), so this test
    pins a floor on per-token agreement, not stream identity — the bench
    gates the margin-confident rate at >= 0.99."""
    cfg, model, params = gqa_model
    rng = np.random.RandomState(22)
    reqs = [Request(rid=i, tokens=rng.randint(0, cfg.vocab_size, 8),
                    max_new_tokens=6) for i in range(4)]
    int8 = _serve_tokens(model, params, reqs, kv_quant="int8")
    bf16 = _serve_tokens(model, params, reqs)
    tok = sum(len(g) for g in bf16)
    same = sum(a == b for g8, gb in zip(int8, bf16)
               for a, b in zip(g8, gb))
    assert all(len(a) == len(b) for a, b in zip(int8, bf16))
    assert same / tok >= 0.75, f"agreement {same}/{tok}"


# ----------------------------------------------------------------------
# Arena contracts on the quantized layout
# ----------------------------------------------------------------------
def _is_qleaf(x):
    return isinstance(x, dict) and set(x) == {"q", "s"}


def _logical_values(arena, total, seed):
    """One random logical (L, total, heads, feat) tensor per paged dict
    leaf (pages are head-major (L, NB, heads, bs, feat)) — same seed =>
    same values, so two arenas fed overlapping position ranges receive
    identical data on the overlap."""
    rngs = [np.random.RandomState(seed + i) for i in range(99)]
    it = iter(rngs)
    return jax.tree.map(
        lambda leaf: next(it).randn(
            leaf["q"].shape[0], total, leaf["q"].shape[2],
            leaf["q"].shape[4]).astype(np.float32),
        arena.buffers, is_leaf=_is_qleaf)


def _quant_scatter(arena, slot, data, p0, n):
    """Quantize-and-scatter positions [p0, p0+n) into ``slot``'s pages
    through its block table — the arena-level image of what the jitted
    step's ``_paged_insert_quant`` does, minus the model."""
    if n <= 0:
        return
    pos = np.arange(p0, p0 + n)
    bs = arena.block_size
    row = arena.tables[slot]
    phys = jnp.asarray(row[pos // bs], jnp.int32)
    offs = jnp.asarray(pos % bs, jnp.int32)

    def ins(leaf, vals):
        if not _is_qleaf(leaf):
            return leaf
        q, s = attn.quantize_kv(jnp.asarray(vals[:, pos]))
        # index dims (positions) lead the update: (n, L, heads[, feat])
        return {"q": leaf["q"].at[:, phys, :, offs].set(
                    jnp.moveaxis(q, 1, 0)),
                "s": leaf["s"].at[:, phys, :, offs].set(
                    jnp.moveaxis(s, 1, 0).astype(leaf["s"].dtype))}
    arena.buffers = jax.tree.map(ins, arena.buffers, data,
                                 is_leaf=_is_qleaf)


def _assert_arenas_bit_identical(a, b):
    np.testing.assert_array_equal(a.tables, b.tables)
    assert a.allocator.free_blocks == b.allocator.free_blocks
    for la, lb, paged in zip(jax.tree.leaves(a.buffers),
                             jax.tree.leaves(b.buffers), a._paged_flags):
        la, lb = np.asarray(la), np.asarray(lb)
        if paged:                      # null page is garbage by contract
            la, lb = la[:, :a.null_block], lb[:, :b.null_block]
        np.testing.assert_array_equal(la, lb)


def _rollback_differential(model, block_size, prefix, m, r, seed):
    """Arena A inserts prefix+m quantized positions then rolls back m-r;
    arena B only ever inserts prefix+r. Codes, scales, tables and free
    lists must all end bit-identical."""
    max_seq = 16
    mk = lambda: PagedKVArena(model, 1, max_seq, block_size=block_size,
                              kv_quant="int8")
    a, b = mk(), mk()
    data = _logical_values(a, prefix + m, seed)
    for arena, n in ((a, prefix + m), (b, prefix + r)):
        slot = arena.alloc_slot(arena.blocks_needed(prefix))
        assert slot == 0
        assert arena.ensure(0, max(n, 1)) is not None
        _quant_scatter(arena, 0, data, 0, n)
    a.rollback(0, prefix + r, m - r, width=max_seq)
    _assert_arenas_bit_identical(a, b)
    assert a.slot_blocks(0) == b.slot_blocks(0)


def test_quant_rollback_bit_identity(gqa_model):
    _, model, _ = gqa_model
    _rollback_differential(model, block_size=4, prefix=5, m=6, r=2, seed=0)


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(st.integers(1, 4), st.integers(0, 10 ** 6))
    def test_fuzz_quant_rollback_bit_identity(block_size, seed):
        """block_size x positions fuzz of the quantized rollback
        contract (module-scope model rebuilt cheaply via the registry —
        hypothesis forbids function-scope fixtures)."""
        cfg = ASSIGNED["qwen3-0.6b"].reduced()
        model = build_model(cfg)
        rng = np.random.RandomState(seed)
        prefix = int(rng.randint(1, 9))
        m = int(rng.randint(1, 16 - prefix + 1))
        r = int(rng.randint(0, m))
        _rollback_differential(model, block_size, prefix, m, r, seed)


def test_quant_cow_split_copies_codes_and_scales(gqa_model):
    """Prefix-sharing on the quantized arena: a fully-cached prompt's
    admission CoW-splits the last chain block, and the split must copy
    the int8 code page AND the fp16 scale page bit-for-bit (the generic
    ``_copy_pages`` walks the expanded leaf list)."""
    cfg, model, params = gqa_model
    bs, L = 4, 8
    arena = PagedKVArena(model, 2, 24, block_size=bs, num_blocks=12,
                         prefix_cache=True, kv_quant="int8")
    rng = np.random.RandomState(9)
    prompt = rng.randint(0, cfg.vocab_size, L)
    slot = arena.alloc_slot(arena.blocks_needed(L))
    data = _logical_values(arena, L, seed=77)
    _quant_scatter(arena, slot, data, 0, L)
    assert arena.register_prefix(slot, prompt) == L // bs

    res = arena.alloc_slot_prefix(prompt, chunk=8)
    assert res is not None
    slot_b, hit, _ = res
    assert hit == L - 1            # whole prompt cached, last pos re-fed
    ba, bb = arena.slot_blocks(slot), arena.slot_blocks(slot_b)
    assert ba[:-1] == bb[:-1] and ba[-1] != bb[-1]   # alias + CoW split
    assert arena.cow_splits == 1
    for leaf in jax.tree.leaves(
            arena.buffers, is_leaf=_is_qleaf):
        if not _is_qleaf(leaf):
            continue
        for part in ("q", "s"):
            np.testing.assert_array_equal(
                np.asarray(leaf[part][:, ba[-1]]),
                np.asarray(leaf[part][:, bb[-1]]))


def test_quant_write_prefill_refused(gqa_model):
    _, model, _ = gqa_model
    arena = PagedKVArena(model, 1, 16, block_size=4, kv_quant="int8")
    with pytest.raises(NotImplementedError, match="quantize-on-insert"):
        arena.write_prefill({}, 0)


def test_quant_arena_block_bytes_ratio(gqa_model):
    """Arena residency: quantized block_bytes() is exactly
    (D + 2) / (2D) of bf16 (int8 codes + fp16 scales vs 2-byte
    elements) — fp16 scales are load-bearing for the <= 0.55 gate."""
    cfg, model, _ = gqa_model
    mk = lambda kvq: PagedKVArena(model, 1, 16, block_size=4,
                                  kv_quant=kvq)
    ratio = mk("int8").block_bytes() / mk("none").block_bytes()
    hd = cfg.resolved_head_dim()
    assert ratio == pytest.approx((hd + 2) / (2 * hd))


def test_page_layout_reports_kv_quant(gqa_model):
    _, model, _ = gqa_model
    arena = PagedKVArena(model, 1, 16, block_size=4, kv_quant="int8")
    lay = arena.page_layout()
    assert lay["kv_quant"] == "int8"
    assert lay["num_pages"] == arena.num_blocks + 1
    assert lay["null_block"] == arena.num_blocks


def test_chunked_step_specs_match_quant_arena(gqa_model):
    """The lowering contract: ``chunked_step_specs(kv_quant="int8")``
    must describe the quantized arena's buffers exactly (shape, dtype
    and pytree structure), or the engine's one-compilation guarantee
    dies at the first step."""
    _, model, _ = gqa_model
    ns, ms, bs, nb = 2, 16, 4, 8
    arena = PagedKVArena(model, ns, ms, block_size=bs, num_blocks=nb,
                         kv_quant="int8")
    specs = model.chunked_step_specs(ns, 4, ms, block_size=bs,
                                     num_blocks=nb, kv_quant="int8")
    spec_leaves, spec_def = jax.tree.flatten(specs["cache"])
    buf_leaves, buf_def = jax.tree.flatten(arena.buffers)
    assert spec_def == buf_def
    for sl, bl in zip(spec_leaves, buf_leaves):
        assert sl.shape == bl.shape and sl.dtype == bl.dtype


# ----------------------------------------------------------------------
# Fail-fast surfaces: one test per refusal
# ----------------------------------------------------------------------
def test_engine_rejects_unknown_kv_quant(gqa_model):
    _, model, params = gqa_model
    with pytest.raises(ValueError, match="kv_quant"):
        ServingEngine(model, params, num_slots=1, max_seq=16,
                      block_size=4, kv_quant="int4")


def test_engine_rejects_kv_quant_without_paging(gqa_model):
    _, model, params = gqa_model
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(model, params, num_slots=1, max_seq=16,
                      kv_quant="int8")


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "jamba-v0.1-52b"])
def test_engine_rejects_kv_quant_recurrent(arch):
    cfg = ASSIGNED[arch].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="recurrent"):
        ServingEngine(model, params, num_slots=1, max_seq=16,
                      block_size=4, kv_quant="int8")


def test_engine_rejects_kv_quant_encdec():
    cfg = ASSIGNED["whisper-small"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="encoder"):
        ServingEngine(model, params, num_slots=1, max_seq=16,
                      block_size=4, kv_quant="int8")


def _args(**over):
    d = dict(arch="qwen3-0.6b", mode="stream", chunk_size=8, block_size=4,
             num_blocks=0, paged_attn=None, spec="off", spec_k=None,
             spec_draft_model=None, kv_quant="int8", prefix_cache=False,
             shared_prefix=0, dp=1, tp=1)
    d.update(over)
    return argparse.Namespace(**d)


def _expect_cli_refusal(args, msg, capsys):
    ap = argparse.ArgumentParser(prog="serve")
    with pytest.raises(SystemExit):
        serve.validate_args(ap, args)
    assert msg in capsys.readouterr().err


def test_cli_kv_quant_requires_block_size(capsys):
    _expect_cli_refusal(_args(block_size=0),
                        "--kv-quant requires the paged arena", capsys)


def test_cli_kv_quant_requires_stream_mode(capsys):
    _expect_cli_refusal(_args(mode="batch"),
                        "--kv-quant requires --mode stream", capsys)


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "jamba-v0.1-52b"])
def test_cli_kv_quant_refuses_recurrent(arch, capsys):
    _expect_cli_refusal(_args(arch=arch),
                        "recurrent state is a running summary", capsys)


def test_cli_kv_quant_refuses_encdec(capsys):
    _expect_cli_refusal(_args(arch="whisper-small"),
                        "one-time encoder pass", capsys)


def test_cli_kv_quant_none_passes():
    ap = argparse.ArgumentParser(prog="serve")
    serve.validate_args(ap, _args(kv_quant="none", block_size=0))
    serve.validate_args(ap, _args())      # int8 + paged + stream is fine
