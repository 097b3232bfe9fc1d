"""Paged KV arena tests: block-allocator properties (hypothesis where
available, deterministic randomized fallbacks otherwise) and the
differential proof that paged decode attention matches the contiguous
reference path — the dense-gather "ref" oracle bit-for-bit at fp32
(same softmax decomposition), the default fused block-table kernel to
tight tolerance (its blocked online softmax is a different-but-equal
factorization; see test_paged_attention_kernel.py for its own suite) —
for both GQA and MLA."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ASSIGNED
from repro.models import attention as attn
from repro.models.api import build_model
from repro.runtime.engine import ServingEngine
from repro.runtime.kvcache import BlockAllocator, KVArena, PagedKVArena
from repro.runtime.request import Request

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAVE_HYPOTHESIS = False


# ----------------------------------------------------------------------
# BlockAllocator: deterministic property checks
# ----------------------------------------------------------------------
def test_allocator_basics():
    a = BlockAllocator(num_blocks=8, block_size=4)
    assert a.free_blocks == 8 and a.used_blocks == 0
    b1 = a.alloc(3)
    assert sorted(b1) == [0, 1, 2]          # lowest ids first
    assert a.alloc(6) is None               # all-or-nothing
    assert a.free_blocks == 5               # failed alloc takes nothing
    b2 = a.alloc(5)
    assert a.free_blocks == 0 and a.alloc(1) is None
    a.free(b1)
    assert a.free_blocks == 3
    with pytest.raises(ValueError):         # double free
        a.free([b1[0]])
    with pytest.raises(ValueError):         # out of range
        a.free([99])
    assert a.reissues == 0
    again = a.alloc(2)
    assert a.reissues == 2                  # previously-freed blocks re-issued
    assert set(again) <= set(b1)


def test_allocator_blocks_for():
    a = BlockAllocator(num_blocks=8, block_size=4)
    assert [a.blocks_for(t) for t in (1, 3, 4, 5, 8, 9)] == [1, 1, 1, 2, 2, 3]


def test_allocator_zero_token_edges():
    a = BlockAllocator(num_blocks=8, block_size=4)
    assert a.blocks_for(0) == 1              # zero tokens still hold a block
    assert a.alloc(0) == []                  # empty claim is legal, takes none
    assert a.free_blocks == 8
    with pytest.raises(ValueError):
        a.alloc(-1)


def _run_alloc_free_trace(num_blocks, block_size, ops):
    """Shared property oracle: replay an op trace against a set-model.

    Invariants: live allocations are pairwise disjoint, free + used ==
    num_blocks at every step, no block is handed out twice while live,
    and per-sequence over-allocation (fragmentation) is < one block."""
    a = BlockAllocator(num_blocks, block_size)
    live = {}                                # handle -> (blocks, tokens)
    next_handle = 0
    for kind, amount in ops:
        if kind == "alloc":
            tokens = max(1, amount)
            got = a.alloc(a.blocks_for(tokens))
            if got is None:
                assert a.free_blocks < a.blocks_for(tokens)
                continue
            held = set().union(*(set(b) for b, _ in live.values())) \
                if live else set()
            assert not (set(got) & held), "double-allocated a live block"
            assert len(set(got)) == len(got)
            # fragmentation bound: waste strictly less than block_size
            assert len(got) * block_size - tokens < block_size
            live[next_handle] = (got, tokens)
            next_handle += 1
        elif live:                           # free the oldest live handle
            h = min(live)
            blocks, _ = live.pop(h)
            a.free(blocks)
        # conservation at every step
        used = sum(len(b) for b, _ in live.values())
        assert a.used_blocks == used
        assert a.free_blocks == num_blocks - used
    for blocks, _ in live.values():
        a.free(blocks)
    assert a.free_blocks == num_blocks       # everything conserved


def test_allocator_random_traces_deterministic():
    """Randomized alloc/free traces without hypothesis (always runs)."""
    for seed in range(8):
        rng = np.random.RandomState(seed)
        num_blocks = int(rng.randint(1, 24))
        block_size = int(rng.randint(1, 9))
        ops = [("alloc" if rng.rand() < 0.6 else "free",
                int(rng.randint(1, 40))) for _ in range(60)]
        _run_alloc_free_trace(num_blocks, block_size, ops)


if HAVE_HYPOTHESIS:
    settings.register_profile("paged", max_examples=40, deadline=None)
    settings.load_profile("paged")

    @given(st.integers(1, 32), st.integers(1, 8),
           st.lists(st.tuples(st.sampled_from(["alloc", "free"]),
                              st.integers(1, 40)), max_size=80))
    def test_allocator_properties_hypothesis(num_blocks, block_size, ops):
        _run_alloc_free_trace(num_blocks, block_size, ops)

    @given(st.integers(1, 16), st.integers(1, 8), st.integers(0, 65))
    def test_blocks_for_covers_minimally(num_blocks, block_size, tokens):
        """blocks_for is the least block count covering ``tokens``
        (floored at one block), down to and including zero tokens."""
        a = BlockAllocator(num_blocks, block_size)
        n = a.blocks_for(tokens)
        assert n >= 1 and n * block_size >= tokens
        assert n == 1 or (n - 1) * block_size < tokens


# ----------------------------------------------------------------------
# PagedKVArena lifecycle (model-backed)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def gqa_model():
    cfg = ASSIGNED["qwen3-0.6b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def mla_model():
    cfg = ASSIGNED["deepseek-v3-671b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    return cfg, model, params


def test_paged_arena_lifecycle(gqa_model):
    cfg, model, params = gqa_model
    arena = PagedKVArena(model, num_slots=3, max_seq=16, block_size=4,
                         num_blocks=6)
    assert arena.max_blocks == 4 and arena.null_block == 6
    # the layout contract the fused paged-attention kernel consumes
    assert arena.page_layout() == {"block_size": 4, "max_blocks": 4,
                                   "num_pages": 7, "local_pages": 7,
                                   "data_shards": 1, "null_block": 6,
                                   "kv_quant": "none"}
    s0 = arena.alloc_slot(2)
    s1 = arena.alloc_slot(3)
    assert {s0, s1} == {0, 1}
    assert arena.alloc_slot(2) is None            # only 1 block left
    assert arena.free_slots == 1                  # failed admit takes nothing
    assert arena.ensure(s0, 8) == 0               # 2 blocks already cover 8
    assert arena.ensure(s0, 9) == 1               # boundary crossing
    assert arena.ensure(s1, 16) is None           # exhausted
    arena.free_slot(s1)
    assert arena.allocator.free_blocks == 3
    assert (arena.tables[s1] == arena.null_block).all()
    # storage: paged leaves are head-major (L, NB+1, Hkv, bs, D),
    # per-slot leaves absent
    leaf = jax.tree.leaves(arena.buffers)[0]
    assert leaf.shape[1] == 7 and leaf.shape[2] == cfg.num_kv_heads
    assert leaf.shape[3] == 4
    assert arena.block_bytes() > 0
    assert arena.resident_bytes() == pytest.approx(
        arena.allocator.used_blocks * arena.block_bytes())


def test_paged_write_prefill_lands_in_reserved_blocks(gqa_model):
    cfg, model, params = gqa_model
    arena = PagedKVArena(model, num_slots=2, max_seq=16, block_size=4)
    slot = arena.alloc_slot(2)                    # covers 8 tokens
    _, cache = model.prefill(params, {"tokens": jnp.ones((1, 8), jnp.int32)})
    before = jax.tree.leaves(arena.buffers)[0].copy()
    arena.write_prefill(cache, slot)
    leaf = jax.tree.leaves(arena.buffers)[0]      # (L, NB+1, H, bs, D)
    phys = arena.slot_blocks(slot)
    for b in phys:
        assert not bool(jnp.array_equal(leaf[:, b], before[:, b]))
    untouched = [b for b in range(leaf.shape[1]) if b not in phys]
    for b in untouched:
        assert bool(jnp.array_equal(leaf[:, b], before[:, b]))


def test_paged_prefill_bucket_overrun_is_dropped(gqa_model):
    """A prompt whose pow2 prefill bucket exceeds its block reservation
    (prompt 10 -> bucket 16 > ceil(10/4)*4 = 12) must not write outside
    its own blocks: the overrun pad is routed to the null page (trash by
    design), never to a neighbor's blocks or the free pool."""
    cfg, model, params = gqa_model
    arena = PagedKVArena(model, num_slots=2, max_seq=24, block_size=4)
    other = arena.alloc_slot(3)
    slot = arena.alloc_slot(3)                    # 12 tokens reserved
    _, cache = model.prefill(params,
                             {"tokens": jnp.ones((1, 16), jnp.int32)})
    before = jax.tree.leaves(arena.buffers)[0].copy()
    arena.write_prefill(cache, slot)
    leaf = jax.tree.leaves(arena.buffers)[0]
    for b in arena.slot_blocks(other):            # neighbor untouched
        assert bool(jnp.array_equal(leaf[:, b], before[:, b]))
    free = set(range(arena.num_blocks)) \
        - set(arena.slot_blocks(other)) - set(arena.slot_blocks(slot))
    for b in free:                                # free pool untouched
        assert bool(jnp.array_equal(leaf[:, b], before[:, b]))


def test_paged_arena_zero_token_edges(gqa_model):
    """Degenerate sizes must hold the arena's invariants: a zero-token
    reservation still pins one block (blocks_needed floor), a zero-block
    slot admission is a legal empty table that grows on demand, and a
    rollback from position 0 trims the whole table."""
    cfg, model, params = gqa_model
    arena = PagedKVArena(model, num_slots=2, max_seq=16, block_size=4,
                         num_blocks=4)
    assert arena.blocks_needed(0) == 1
    slot = arena.alloc_slot(0)               # admitted with an empty table
    assert slot is not None and arena.slot_blocks(slot) == []
    assert (arena.tables[slot] == arena.null_block).all()
    assert arena.ensure(slot, 5) == 2        # grows from empty
    arena.free_slot(slot)
    assert arena.allocator.free_blocks == 4


def test_paged_rollback_from_position_zero(gqa_model):
    cfg, model, params = gqa_model
    arena = PagedKVArena(model, num_slots=1, max_seq=16, block_size=4,
                         num_blocks=4)
    slot = arena.alloc_slot(2)
    assert arena.rollback(slot, 0, 0, width=8) == 0    # empty span: no-op
    assert arena.slot_blocks(slot) != []
    dropped = arena.rollback(slot, 0, 8, width=8)      # reject everything
    assert dropped == 2
    assert arena.slot_blocks(slot) == []
    assert (arena.tables[slot] == arena.null_block).all()
    assert arena.allocator.free_blocks == 4


def test_request_rejects_degenerate_prompts():
    """The runtime contract is prompts >= 2 tokens (the final prompt
    token is decoded, so 0- and 1-token prompts have no feedable
    prefix); rejection happens at Request construction, not mid-serve."""
    with pytest.raises(ValueError):
        Request(rid=0, tokens=np.array([], np.int32), max_new_tokens=1)
    with pytest.raises(ValueError):
        Request(rid=0, tokens=np.array([5], np.int32), max_new_tokens=1)
    assert Request(rid=0, tokens=np.array([5, 6], np.int32),
                   max_new_tokens=1).prompt_len == 2


# ----------------------------------------------------------------------
# Differential: paged decode == contiguous decode (GQA + MLA)
# ----------------------------------------------------------------------
def _scatter_to_pages(contig, tables, bs, nb):
    """(B, S, H, ...) -> a one-layer stack of head-major (1, NB+1, H, bs,
    ...) pages per a (B, MB) block table; a cache without a head axis
    (MLA latents) gets the unit one."""
    if contig.ndim == 3:
        contig = contig[:, :, None]
    pages = np.zeros((nb + 1, contig.shape[2], bs) + contig.shape[3:],
                     contig.dtype)
    for i in range(contig.shape[0]):
        for j in range(tables.shape[1]):
            pages[tables[i, j]] = np.swapaxes(
                np.asarray(contig[i, j * bs:(j + 1) * bs]), 0, 1)
    return jnp.asarray(pages[None])


def _random_tables(rng, b, mb, nb):
    perm = rng.permutation(nb)
    return np.stack([perm[i * mb:(i + 1) * mb]
                     for i in range(b)]).astype(np.int32)


@pytest.mark.parametrize("dtype,exact", [(jnp.float32, True),
                                         (jnp.bfloat16, False)])
def test_paged_gqa_decode_matches_contiguous(gqa_model, dtype, exact):
    cfg, _, _ = gqa_model
    key = jax.random.PRNGKey(0)
    p = attn.gqa_init(key, cfg)
    B, S, bs = 3, 16, 4
    mb, nb = S // bs, 3 * (S // bs)
    hd, hkv = cfg.resolved_head_dim(), cfg.num_kv_heads
    k1, k2, k3 = jax.random.split(key, 3)
    kc = jax.random.normal(k1, (B, S, hkv, hd), dtype)
    vc = jax.random.normal(k2, (B, S, hkv, hd), dtype)
    x = jax.random.normal(k3, (B, 1, cfg.d_model), dtype)
    positions = jnp.array([5, 9, 2], jnp.int32)

    out_c, cache_c = attn.gqa_decode(p, cfg, x, positions,
                                     {"k": kc, "v": vc})
    tables = _random_tables(np.random.RandomState(0), B, mb, nb)
    paged_cache = {"k": _scatter_to_pages(kc, tables, bs, nb),
                   "v": _scatter_to_pages(vc, tables, bs, nb)}
    # The dense-gather oracle ("ref") is the bit-exactness anchor: same
    # softmax decomposition as the contiguous path. The fused kernel has
    # its own differential suite (test_paged_attention_kernel.py).
    out_p, cache_p = attn.gqa_decode(p, cfg, x, positions, paged_cache,
                                     block_tables=jnp.asarray(tables),
                                     paged_impl="ref")
    out_f, _ = attn.gqa_decode(p, cfg, x, positions, paged_cache,
                               block_tables=jnp.asarray(tables))
    if exact:
        np.testing.assert_array_equal(np.asarray(out_c), np.asarray(out_p),
                                      err_msg="fp32 paged GQA != contiguous")
        np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_f),
                                   atol=1e-5, rtol=1e-4)
    else:
        np.testing.assert_allclose(np.asarray(out_c, np.float32),
                                   np.asarray(out_p, np.float32),
                                   atol=5e-2, rtol=5e-2)
        np.testing.assert_allclose(np.asarray(out_c, np.float32),
                                   np.asarray(out_f, np.float32),
                                   atol=5e-2, rtol=5e-2)
    # the inserted token is readable back through the table at each slot
    view = attn.paged_view(cache_p["k"], jnp.asarray(tables))
    for i in range(B):
        pos = int(positions[i])
        np.testing.assert_array_equal(np.asarray(view[i, pos]),
                                      np.asarray(cache_c["k"][i, pos]))


@pytest.mark.parametrize("dtype,exact", [(jnp.float32, True),
                                         (jnp.bfloat16, False)])
def test_paged_mla_decode_matches_contiguous(mla_model, dtype, exact):
    cfg, _, _ = mla_model
    m = cfg.mla
    key = jax.random.PRNGKey(1)
    p = attn.mla_init(key, cfg)
    B, S, bs = 2, 16, 4
    mb, nb = S // bs, 2 * (S // bs)
    k1, k2, k3 = jax.random.split(key, 3)
    ckv = jax.random.normal(k1, (B, S, m.kv_lora_rank), dtype)
    kr = jax.random.normal(k2, (B, S, m.qk_rope_head_dim), dtype)
    x = jax.random.normal(k3, (B, 1, cfg.d_model), dtype)
    positions = jnp.array([7, 3], jnp.int32)

    out_c, _ = attn.mla_decode(p, cfg, x, positions,
                               {"ckv": ckv, "krope": kr})
    tables = _random_tables(np.random.RandomState(1), B, mb, nb)
    paged_cache = {"ckv": _scatter_to_pages(ckv, tables, bs, nb),
                   "krope": _scatter_to_pages(kr, tables, bs, nb)}
    out_p, _ = attn.mla_decode(p, cfg, x, positions, paged_cache,
                               block_tables=jnp.asarray(tables),
                               paged_impl="ref")
    out_f, _ = attn.mla_decode(p, cfg, x, positions, paged_cache,
                               block_tables=jnp.asarray(tables))
    if exact:
        np.testing.assert_array_equal(np.asarray(out_c), np.asarray(out_p),
                                      err_msg="fp32 paged MLA != contiguous")
        np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_f),
                                   atol=1e-5, rtol=1e-4)
    else:
        np.testing.assert_allclose(np.asarray(out_c, np.float32),
                                   np.asarray(out_p, np.float32),
                                   atol=1e-1, rtol=1e-1)
        np.testing.assert_allclose(np.asarray(out_c, np.float32),
                                   np.asarray(out_f, np.float32),
                                   atol=1e-1, rtol=1e-1)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("heads", [2, 1])        # GQA K/V; MLA latents
@pytest.mark.parametrize("kv", ["float32", "int8"])
@pytest.mark.parametrize("feed", ["chunk", "token"])
def test_paged_insert_in_place_at_layer(feed, kv, heads, layer):
    """The insert writes row (layer, page, head, offset) of the stacked
    arena for each valid (slot, chunk entry) and nothing else: every
    other layer, every untouched row and every dropped tail write stay
    bit-identical. A chunk that crosses a block edge puts entries of one
    slot on two pages, so its writes span two tiles."""
    L, nb, bs, d = 3, 10, 4, 8
    rng = np.random.RandomState(23 + layer + 2 * heads)
    tables = np.stack([rng.permutation(nb)[:3] for _ in range(3)]) \
        .astype(np.int32)
    tables[2] = nb                               # an idle slot: null page
    if feed == "chunk":                          # b0 crosses a block edge
        c, pos0, lengths = 5, [2, 5, 0], jnp.asarray([5, 2, 0], jnp.int32)
    else:
        c, pos0, lengths = 1, [6, 3, 1], None
    new = rng.randn(3, c, heads, d).astype(np.float32)
    dense = rng.randn(L, nb + 1, heads, bs, d).astype(np.float32)
    if kv == "int8":
        q, sc = attn.quantize_kv(jnp.asarray(dense))
        pages = {"q": q, "s": sc}
        got = attn._paged_insert_quant(pages, jnp.asarray(new),
                                       jnp.asarray(pos0), jnp.asarray(tables),
                                       lengths, layer)
        nq, ns = attn.quantize_kv(jnp.asarray(new))
        writes = {"q": np.asarray(nq), "s": np.asarray(ns)}
    else:
        pages = {"x": jnp.asarray(dense)}
        got = {"x": attn.paged_insert_token(
            pages["x"], jnp.asarray(new), jnp.asarray(pos0),
            jnp.asarray(tables), lengths, layer)}
        writes = {"x": new}
    n = [c] * 3 if lengths is None else list(np.asarray(lengths))
    for part, arr in pages.items():
        want = np.array(arr)
        for b in range(3):
            for i in range(n[b]):
                pos = pos0[b] + i
                want[layer, tables[b, pos // bs], :, pos % bs] = \
                    writes[part][b, i]
        np.testing.assert_array_equal(np.asarray(got[part]), want)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "deepseek-v3-671b"])
def test_paged_serve_tokens_match_contiguous(arch, gqa_model, mla_model):
    """End-to-end differential: the same greedy request stream through the
    paged engine and the contiguous engine emits identical tokens (prefill
    scatter, mid-decode block growth, slot turnover included)."""
    cfg, model, params = gqa_model if arch == "qwen3-0.6b" else mla_model
    rng = np.random.RandomState(2)
    mk = lambda: [Request(rid=i,
                          tokens=rng.randint(0, cfg.vocab_size,
                                             int(lens[i])),
                          max_new_tokens=4) for i in range(5)]
    lens = rng.randint(4, 12, size=5)
    reqs_a, reqs_b = mk(), mk()
    # identical prompts for both engines
    for a, b in zip(reqs_a, reqs_b):
        b.tokens = a.tokens.copy()
    cont = ServingEngine(model, params, num_slots=2, max_seq=24)
    paged = ServingEngine(model, params, num_slots=2, max_seq=24,
                          block_size=4)
    rc = cont.serve(reqs_a, seed=0, realtime=False)
    rp = paged.serve(reqs_b, seed=0, realtime=False)
    assert rp.step_compiles <= 1
    for a, b in zip(rc.sequences, rp.sequences):
        assert a.rid == b.rid
        assert a.generated == b.generated, \
            f"request {a.rid} diverged between paged and contiguous decode"


def test_paged_decode_specs_match_engine_inputs(gqa_model):
    """AOT-spec drift guard: ModelAPI.paged_decode_specs must describe
    exactly the shapes/dtypes the paged engine feeds its jitted step."""
    cfg, model, params = gqa_model
    ns, nb, bs, ms = 3, 6, 4, 16
    eng = ServingEngine(model, params, num_slots=ns, max_seq=ms,
                        block_size=bs, num_blocks=nb)
    specs = model.paged_decode_specs(ns, nb, bs, ms)
    assert specs["token"].shape == (ns, 1)
    assert specs["positions"].shape == (ns,)
    assert specs["active"].shape == (ns,)
    tables, _ = eng.arena.device_tables()
    assert specs["block_tables"].shape == tables.shape
    assert specs["block_tables"].dtype == tables.dtype
    spec_leaves = jax.tree.leaves(specs["cache"])
    buf_leaves = jax.tree.leaves(eng.arena.buffers)
    assert len(spec_leaves) == len(buf_leaves)
    for s, b in zip(spec_leaves, buf_leaves):
        assert s.shape == b.shape and s.dtype == b.dtype


def test_paged_arena_capacity_check(gqa_model):
    """A request that could never finish even alone is rejected upfront
    (livelock guard for the preemption loop) — but peak demand is
    prompt+gen-1 positions (the last sampled token is never inserted), so
    a request landing exactly on that boundary is accepted and finishes."""
    cfg, model, params = gqa_model
    eng = ServingEngine(model, params, num_slots=2, max_seq=32,
                        block_size=4, num_blocks=3)
    req = Request(rid=0, tokens=np.arange(10) % cfg.vocab_size,
                  max_new_tokens=10)    # peak 19 positions -> 5 blocks > 3
    with pytest.raises(ValueError):
        eng.serve([req], seed=0, realtime=False)
    # prompt 9 + gen 4: peak demand ceil(12/4) == 3 blocks — exactly fits
    eng = ServingEngine(model, params, num_slots=1, max_seq=32,
                        block_size=4, num_blocks=3)
    rep = eng.serve([Request(rid=0, tokens=np.arange(9) % cfg.vocab_size,
                             max_new_tokens=4)], seed=0, realtime=False)
    assert rep.sched.completed == 1
    assert rep.sequences[0].tokens_out == 4


def test_paged_preemption_completes_all(gqa_model):
    """Scarce blocks force mid-decode preemption; every request still
    finishes, greedy tokens match an uncontended run, nothing leaks."""
    cfg, model, params = gqa_model
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab_size, 8) for _ in range(4)]
    reqs = [Request(rid=i, tokens=p.copy(), max_new_tokens=10)
            for i, p in enumerate(prompts)]
    eng = ServingEngine(model, params, num_slots=4, max_seq=24,
                        block_size=4, num_blocks=6)
    rep = eng.serve(reqs, seed=0, realtime=False)
    assert rep.sched.completed == 4
    assert rep.sched.preemptions > 0
    assert eng.arena.allocator.free_blocks == 6
    assert eng.arena.free_slots == 4
    # uncontended contiguous run: greedy tokens must survive preemption
    ref_eng = ServingEngine(model, params, num_slots=4, max_seq=24)
    ref = ref_eng.serve([Request(rid=i, tokens=p.copy(), max_new_tokens=10)
                         for i, p in enumerate(prompts)],
                        seed=0, realtime=False)
    for got, want in zip(rep.sequences, ref.sequences):
        assert got.generated == want.generated
