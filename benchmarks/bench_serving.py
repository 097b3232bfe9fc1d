"""Continuous-batching serving benchmark: throughput vs batch occupancy,
the paging win at equal arena bytes, and the chunked-prefill transfer win
at equal workload.

Part 1 replays the same request stream through the slot-arena engine at
several arena sizes and reports decode throughput, mean occupancy,
per-request latency percentiles, and the transfer ledger's
bytes-per-token — the live analog of the paper's §V.A
transfer-bottleneck analysis.

Part 2 holds the KV **storage bytes fixed** and compares the
whole-sequence slot arena against the paged block-table arena on a
short-request stream: max concurrent sequences, bytes *resident* per
live cache token, preemptions, and decode-step compiles (paging must not
re-jit).

Part 3 holds the **workload fixed** and compares the unified
chunked-prefill step against the *analytic bucketed replay* (the
retired legacy engine's exact per-request charges — pow2 prefill
buckets + per-sequence weight restream — replayed through the same
ledger): prefill bytes/token (no pow2 padding, co-prefilling slots
share one weight pass) and total bytes/token (the per-step shared
weight stream replaces the per-slot restream), with ``step_compiles ==
1`` across the mixed-length stream.

Part 4 holds the **live tokens fixed** and grows the paged arena
capacity (``--num-blocks`` / table width): the fused block-table
paged-attention kernel's per-step KV read traffic — accounted from the
engine's real tables and positions each step — must NOT scale with the
arena (O(live tokens)), while the ``paged_attn="ref"`` dense gather
scales linearly (O(arena)). This is the ISSUE 4 acceptance metric.

Part 5 holds the **workload fixed** (repetitive-suffix prompts, long
greedy generations — the reduced model's decode settles into repeating
cycles, exactly what prompt-lookup drafting exploits) and compares
``spec=off`` against the n-gram speculative path at k=4: outputs must be
token-for-token identical, and the *weight-stream* bytes per generated
token — the per-step shared linear DMA stream, the paper's dominant
transfer term — must drop below 0.7x, because each verify step commits
accept_len + 1 tokens against one stream. This is the ISSUE 5 acceptance
metric, gated alongside the accept rate.

Part 6 is the ISSUE 7 acceptance: refcounted copy-on-write prefix
sharing. A shared-prefix workload (every request opens with the same
long system prompt) runs against a warm prefix cache: prompt tokens
mapped from cached pages are never re-streamed, so warm prefill h2d
bytes must drop to <= 0.1x an unshared engine at the same concurrency,
outputs must stay token-for-token identical (greedy) across
unshared / cold-cache / warm-cache runs, and at equal arena bytes the
deduplicated prefix must lift admission capacity > 3.5x over the
contiguous arena.

Part 7 is the ISSUE 8 acceptance: int8 quantized KV pages with
in-kernel dequant. At equal workload AND schedule, the paged int8
engine's per-slot KV stream bytes/token must drop to <= 0.55x the bf16
paged engine — the exact factor is (head_dim + 2) / (2 * head_dim):
int8 codes plus one fp16 scale per (position, kv-head) replace 2-byte
elements (see docs/transfer-ledger.md) — and e2e greedy token agreement
(teacher-forced against bf16 rollouts, margin-confident positions) must
stay >= 0.99.

Part 8 is the sharded-serving acceptance: the same stream served
through a 1x2 ('data' x 'model') mesh must be token-identical to the
unsharded engine with one step compile, the aggregate ledger must not
move (committed baselines are degree-invariant by construction), and
the *per-device* weight-stream bytes/token — each device streams only
its out-feature shard of every linear — must drop to <= 0.55x TP=1
(exact factor 1/tp). Runs in a subprocess under forced host devices.

Part 9 is the ISSUE 10 acceptance: per-step serving telemetry. The
instrumented engine must be observationally free — telemetry on/off
serves are token-identical with one step compile — while the timeline's
summed per-step ledger deltas close against ``TransferLedger.breakdown``
as exact dict equality, the JSONL/Perfetto exports pass their schema
validators, and the streaming latency histogram sits within its
geometric-bin error bound of the exact quantiles.

Runs on the reduced model (CPU-friendly); the analytic full-size numbers
live in bench_e2e_latency.py. ``--json PATH`` writes the CI benchmark-
regression metrics (see .github/workflows/ci.yml and
benchmarks/check_bench_regression.py).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, fmt_percentiles
from repro.configs.registry import ASSIGNED
from repro.models.api import build_model
from repro.runtime.engine import ServingEngine
from repro.runtime.kvcache import KVArena
from repro.runtime.request import Request
from repro.runtime.transfers import bucketed_replay_ledger

ARCH = "qwen3-0.6b"
N_REQUESTS = 8
GEN = 8
PROMPT_MAX = 16
SLOT_SWEEP = (1, 2, 4, 8)
CHUNK = 16          # >= PROMPT_MAX: every prompt ingests in one shared step

# Equal-bytes paging comparison: contiguous 2 slots x 32 tokens vs paged
# 8 blocks x 8 tokens (block_size == max_seq/4) with 8 slot lanes.
PAGED_MAX_SEQ = 32
PAGED_BLOCK = 8
CONT_SLOTS = 2
PAGED_SLOTS = 8

METRICS = {}


def make_requests(cfg, rng: np.random.RandomState, n=N_REQUESTS,
                  lo=4, hi=PROMPT_MAX, gen=GEN):
    reqs = []
    for i in range(n):
        L = int(rng.randint(lo, hi + 1))
        reqs.append(Request(rid=i, tokens=rng.randint(0, cfg.vocab_size, L),
                            max_new_tokens=gen))
    return reqs


def occupancy_sweep(cfg, model, params) -> None:
    """Part 1, telemetry-instrumented since ISSUE 10: the latency
    percentiles come from the streaming ``LogHistogram`` (the serving
    front end's estimator, <= ~10% geometric-bin error) instead of the
    exact post-hoc sort — CI gates the same number the live report
    shows."""
    for slots in SLOT_SWEEP:
        engine = ServingEngine(model, params, num_slots=slots,
                               max_seq=PROMPT_MAX + GEN, chunk_size=CHUNK,
                               telemetry=True)
        reqs = make_requests(cfg, np.random.RandomState(0))
        report = engine.serve(reqs, seed=0)
        st = report.stats
        pct = report.timeline.hists["request_latency_s"].percentiles(
            (50, 99))
        emit(f"serving/{ARCH}/slots{slots}/throughput",
             st.e2e_s / max(st.decode_tokens, 1) * 1e6,
             f"tok_per_s={report.throughput_tok_s:.2f} "
             f"occupancy={report.sched.mean_occupancy:.2f} "
             f"reuses={report.sched.slot_reuses} "
             f"{fmt_percentiles(pct)} "
             f"bytes_per_tok_MB={report.transfers.bytes_per_token/1e6:.3f} "
             f"step_compiles={report.step_compiles}")
        if slots == 4:
            METRICS["p50_latency_s"] = pct["p50"]
            METRICS["throughput_tok_s"] = report.throughput_tok_s
            METRICS["step_compiles"] = report.step_compiles


def paging_comparison(cfg, model, params) -> None:
    """Whole-sequence slots vs paged blocks at equal KV storage bytes.
    The paged arena's +1 null page comes out of its block budget, so the
    physical storage (arena.nbytes()) is byte-identical, not just
    logical-capacity-identical."""
    short = dict(n=12, lo=4, hi=6, gen=3)      # ~1 block per sequence
    num_blocks = CONT_SLOTS * PAGED_MAX_SEQ // PAGED_BLOCK - 1  # -1: null pg
    runs = {
        "contiguous": ServingEngine(model, params, num_slots=CONT_SLOTS,
                                    max_seq=PAGED_MAX_SEQ,
                                    chunk_size=CHUNK),
        "paged": ServingEngine(model, params, num_slots=PAGED_SLOTS,
                               max_seq=PAGED_MAX_SEQ,
                               block_size=PAGED_BLOCK,
                               num_blocks=num_blocks, chunk_size=CHUNK),
    }
    assert runs["paged"].arena.nbytes() == runs["contiguous"].arena.nbytes()
    results = {}
    for name, engine in runs.items():
        reqs = make_requests(cfg, np.random.RandomState(2), **short)
        report = engine.serve(reqs, seed=0, realtime=False)
        st = report.stats
        results[name] = report
        emit(f"serving/{ARCH}/equal_bytes/{name}/max_concurrent",
             report.sched.max_occupancy,
             f"mean_occupancy={report.sched.mean_occupancy:.2f} "
             f"resident_bytes_per_tok={st.resident_bytes_per_token:.0f} "
             f"peak_resident_MB={st.peak_resident_bytes/1e6:.3f} "
             f"preemptions={report.sched.preemptions} "
             f"step_compiles={report.step_compiles}")
    ratio = results["paged"].sched.max_occupancy \
        / max(results["contiguous"].sched.max_occupancy, 1)
    emit(f"serving/{ARCH}/equal_bytes/concurrency_gain", ratio,
         f"paged={results['paged'].sched.max_occupancy} "
         f"contiguous={results['contiguous'].sched.max_occupancy} "
         f"(acceptance: >= 2x at block_size <= max_seq/4)")
    METRICS["equal_bytes_concurrency_gain"] = ratio


def chunked_comparison(cfg, model, params) -> None:
    """Equal-workload chunked vs the analytic bucketed replay.

    The retired bucketed engine's ledger charges were per-slot and
    additive (``charge_prefill`` per request at its pow2 bucket,
    ``charge_decode_step`` per generated token at its KV depth), so
    replaying them through a fresh ledger reproduces exactly what that
    engine charged for this stream at any occupancy — no legacy engine
    needed to keep the comparison honest."""
    mk = lambda: make_requests(cfg, np.random.RandomState(5), lo=5)
    reqs = mk()
    max_seq = PROMPT_MAX + GEN
    led_b = bucketed_replay_ledger(
        cfg, "none", [(r.prompt_len, r.max_new_tokens) for r in reqs],
        max_seq)

    engine = ServingEngine(model, params, num_slots=4, max_seq=max_seq,
                           chunk_size=CHUNK)
    rc = engine.serve(mk(), seed=0, realtime=False)
    pre_b = led_b.phase_bytes("prefill")["h2d"]
    pre_c = rc.transfers.phase_totals["prefill"]["h2d"]
    emit(f"serving/{ARCH}/prefill_bucketed_replay/bytes_per_token",
         led_b.bytes_per_token(),
         f"prefill_h2d_per_prompt_tok="
         f"{pre_b / max(led_b.tokens['prefill'], 1):.0f} "
         f"prefill_tokens={led_b.tokens['prefill']} (analytic replay)")
    emit(f"serving/{ARCH}/prefill_chunked/bytes_per_token",
         rc.transfers.bytes_per_token,
         f"prefill_h2d_per_prompt_tok="
         f"{pre_c / max(rc.ledger.tokens['prefill'], 1):.0f} "
         f"prefill_tokens={rc.ledger.tokens['prefill']} "
         f"step_compiles={rc.step_compiles}")
    METRICS["bytes_per_token"] = rc.transfers.bytes_per_token
    METRICS["prefill_h2d_bytes"] = pre_c
    METRICS["chunked_vs_bucketed_bytes_ratio"] = \
        rc.transfers.bytes_per_token / led_b.bytes_per_token()
    METRICS["chunked_vs_bucketed_prefill_ratio"] = pre_c / pre_b
    METRICS["chunked_step_compiles"] = rc.step_compiles
    # bytes/token decomposition: the shareable linear weight stream vs
    # the per-slot KV traffic (what speculative verification amortizes
    # vs what it cannot), plus the steps-per-token ratio behind it.
    METRICS["weight_stream_bytes_per_token"] = \
        rc.transfers.weight_stream_bytes_per_token
    METRICS["kv_stream_bytes_per_token"] = \
        rc.transfers.kv_stream_bytes / max(rc.stats.decode_tokens, 1)
    METRICS["steps_per_token"] = rc.stats.steps_per_token
    emit(f"serving/{ARCH}/chunked_vs_bucketed/bytes_ratio",
         METRICS["chunked_vs_bucketed_bytes_ratio"],
         f"prefill_ratio={METRICS['chunked_vs_bucketed_prefill_ratio']:.3f} "
         f"(acceptance: both < 1.0; bucketed side is the analytic replay)")


def paged_attn_scaling(cfg, model, params) -> None:
    """ISSUE 4 acceptance: fixed live tokens, 4x the arena capacity
    (max_seq 32 -> 128, num_blocks 8 -> 32, table width 4 -> 16). The
    fused kernel's paged KV read bytes/token must not move (its clamped
    block-table walk touches only live blocks); the ref gather's scale
    with the table width."""
    streams = lambda: make_requests(cfg, np.random.RandomState(7),
                                    n=6, lo=4, hi=8, gen=4)
    per_tok = {}
    for cap_name, (ms, nb) in (("1x", (32, 8)), ("4x", (128, 32))):
        for impl in ("fused", "ref"):
            eng = ServingEngine(model, params, num_slots=2, max_seq=ms,
                                block_size=8, num_blocks=nb, chunk_size=4,
                                paged_attn=impl)
            rep = eng.serve(streams(), seed=0, realtime=False)
            assert rep.sched.completed == 6
            bpt = rep.stats.paged_kv_read_bytes \
                / max(rep.stats.decode_tokens, 1)
            per_tok[impl, cap_name] = bpt
            emit(f"serving/{ARCH}/paged_attn_{impl}/arena_{cap_name}"
                 f"/kv_read_bytes_per_token", bpt,
                 f"max_seq={ms} num_blocks={nb} "
                 f"step_compiles={rep.step_compiles}")
    fused_ratio = per_tok["fused", "4x"] / per_tok["fused", "1x"]
    ref_ratio = per_tok["ref", "4x"] / per_tok["ref", "1x"]
    METRICS["paged_fused_read_bytes_arena_scaling"] = fused_ratio
    METRICS["paged_ref_read_bytes_arena_scaling"] = ref_ratio
    METRICS["paged_fused_vs_ref_read_bytes"] = \
        per_tok["fused", "4x"] / per_tok["ref", "4x"]
    emit(f"serving/{ARCH}/paged_attn/arena_scaling", fused_ratio,
         f"fused_4x_over_1x={fused_ratio:.3f} (acceptance: ~1.0, "
         f"O(live tokens)) ref_4x_over_1x={ref_ratio:.3f} (O(arena)) "
         f"fused_vs_ref_at_4x={METRICS['paged_fused_vs_ref_read_bytes']:.3f}")


def speculative_amortization(cfg, model, params) -> None:
    """ISSUE 5 acceptance: n-gram speculative decoding vs plain serve on
    a repetitive-suffix workload (tiled 4-token prompt patterns + long
    greedy generations — the reduced model's greedy decode settles into
    repeating cycles, which is exactly the structure prompt-lookup
    drafting proposes from). Outputs must match token-for-token; the
    weight-stream bytes per generated token must drop below 0.7x because
    each verify step commits accept_len + 1 tokens against ONE shared
    linear-weight stream. All gated numbers are modeled-ledger
    deterministic (greedy, CPU)."""
    def mk():
        rng = np.random.RandomState(11)
        reqs = []
        for i in range(6):
            pat = rng.randint(0, cfg.vocab_size, 4)
            reqs.append(Request(rid=i, tokens=np.tile(pat, 2),
                                max_new_tokens=64))
        return reqs

    runs = {}
    for mode in ("off", "ngram"):
        eng = ServingEngine(model, params, num_slots=2, max_seq=72,
                            chunk_size=8, spec=mode, spec_k=4)
        runs[mode] = eng.serve(mk(), seed=0, realtime=False)
    off, ng = runs["off"], runs["ngram"]
    for a, b in zip(off.sequences, ng.sequences):
        assert a.generated == b.generated, \
            f"greedy spec diverged from non-spec on request {a.rid}"
    wpt = {m: r.stats.transfers.weight_stream_bytes_per_token
           for m, r in runs.items()}
    ratio = wpt["ngram"] / wpt["off"]
    st = ng.stats
    METRICS["spec_weight_stream_ratio"] = ratio
    METRICS["spec_accept_rate"] = st.spec_accept_rate
    METRICS["spec_steps_per_token"] = st.steps_per_token
    METRICS["spec_step_compiles"] = ng.step_compiles
    for m, r in runs.items():
        emit(f"serving/{ARCH}/spec_{m}/weight_stream_bytes_per_token",
             wpt[m],
             f"steps_per_token={r.stats.steps_per_token:.3f} "
             f"bytes_per_tok_MB={r.transfers.bytes_per_token/1e6:.3f} "
             f"step_compiles={r.step_compiles}")
    emit(f"serving/{ARCH}/spec_ngram/weight_stream_ratio", ratio,
         f"accept_rate={st.spec_accept_rate:.3f} "
         f"proposed={st.spec_proposed} accepted={st.spec_accepted} "
         f"rolled_back={st.spec_rolled_back} "
         f"(acceptance: < 0.7 at k=4, token-for-token identical)")


def prefix_sharing(cfg, model, params) -> None:
    """ISSUE 7 acceptance: refcounted copy-on-write prefix sharing.

    Part A holds the workload fixed — 8 requests opening with the same
    60-token system prompt (15 full blocks of 4); half are exactly the
    prompt (full-hit: the last chain block is split copy-on-write at
    admission), half add a 2-token tail — and compares a warm prefix
    cache against an unshared engine at the same concurrency. Prompt
    tokens served from shared pages never stream through the step, so
    the warm run's prefill phase collapses to one step (re-feeding only
    the uncached tail), and its prefill h2d bytes must drop to <= 0.1x
    unshared. Outputs are pinned token-for-token identical (greedy)
    across unshared / cold / warm, and the warm run must not re-jit.

    Part B holds the arena bytes fixed (paged 15+1 null blocks x 4 ==
    contiguous 2 slots x 32) on a 12-request shared-prefix stream: with
    the 7 prefix blocks deduplicated every admission costs one private
    block, so the paged+cache arena sustains 8 concurrent sequences
    where the contiguous arena fits 2 (acceptance: > 3.5x)."""
    P_LEN, P_BS, P_GEN = 60, 4, 2

    def mk():
        rng = np.random.RandomState(13)
        sys_prompt = rng.randint(0, cfg.vocab_size, P_LEN)
        reqs = []
        for i in range(8):
            toks = sys_prompt if i % 2 == 0 else np.concatenate(
                [sys_prompt, rng.randint(0, cfg.vocab_size, 2)])
            reqs.append(Request(rid=i, tokens=toks, max_new_tokens=P_GEN))
        return reqs

    mk_eng = lambda nb, pc: ServingEngine(
        model, params, num_slots=8, max_seq=P_LEN + 4, block_size=P_BS,
        num_blocks=nb, chunk_size=4, paged_attn="fused", prefix_cache=pc)
    unshared = mk_eng(160, False)   # sized for 8-way unshared residency
    ru = unshared.serve(mk(), seed=0, realtime=False)
    shared = mk_eng(32, True)       # shared prefix fits 8-way in 32 blocks
    rcold = shared.serve(mk(), seed=0, realtime=False)   # seeds the cache
    rwarm = shared.serve(mk(), seed=0, realtime=False)   # every admission hits
    for run_name, r in (("cold", rcold), ("warm", rwarm)):
        assert r.sched.completed == 8
        for a, b in zip(ru.sequences, r.sequences):
            assert a.generated == b.generated, \
                f"greedy {run_name}-cache serve diverged from unshared " \
                f"on request {a.rid}"
    assert rwarm.stats.prefix_hits == 8, rwarm.stats.prefix_hits
    h2d_u = ru.transfers.phase_totals["prefill"]["h2d"]
    h2d_w = rwarm.transfers.phase_totals["prefill"]["h2d"]
    ratio = h2d_w / h2d_u
    ptoks = sum(r.prompt_len for r in mk())
    emit(f"serving/{ARCH}/prefix_unshared/prefill_h2d_per_prompt_token",
         h2d_u / ptoks, f"prefill_h2d_MB={h2d_u/1e6:.3f} "
         f"prompt_tokens={ptoks} step_compiles={ru.step_compiles}")
    emit(f"serving/{ARCH}/prefix_warm/prefill_h2d_per_prompt_token",
         h2d_w / ptoks,
         f"prefill_h2d_MB={h2d_w/1e6:.3f} hits={rwarm.stats.prefix_hits}/8 "
         f"hit_tokens={rwarm.stats.prefix_hit_tokens} "
         f"cow_splits={rwarm.stats.cow_splits} "
         f"step_compiles={rwarm.step_compiles}")
    emit(f"serving/{ARCH}/prefix_warm/prefill_h2d_ratio", ratio,
         "(acceptance: <= 0.1x unshared; shared pages are mapped, "
         "never re-streamed; outputs pinned token-identical in-bench)")
    METRICS["prefix_hit_prefill_h2d_ratio"] = ratio
    METRICS["prefix_cache_step_compiles"] = rwarm.step_compiles

    def mkb():
        rng = np.random.RandomState(17)
        sys_prompt = rng.randint(0, cfg.vocab_size, 28)   # 7 full blocks
        return [Request(rid=i, tokens=np.concatenate(
                    [sys_prompt, rng.randint(0, cfg.vocab_size, 2)]),
                    max_new_tokens=2) for i in range(12)]

    nb = CONT_SLOTS * PAGED_MAX_SEQ // P_BS - 1          # -1: null page
    cont = ServingEngine(model, params, num_slots=CONT_SLOTS,
                         max_seq=PAGED_MAX_SEQ, chunk_size=CHUNK)
    paged = ServingEngine(model, params, num_slots=8,
                          max_seq=PAGED_MAX_SEQ, block_size=P_BS,
                          num_blocks=nb, chunk_size=CHUNK,
                          paged_attn="fused", prefix_cache=True)
    assert paged.arena.nbytes() == cont.arena.nbytes()
    r_cont = cont.serve(mkb(), seed=0, realtime=False)
    paged.serve(mkb(), seed=0, realtime=False)           # cold: seeds cache
    r_paged = paged.serve(mkb(), seed=0, realtime=False)
    assert r_cont.sched.completed == 12
    assert r_paged.sched.completed == 12
    gain = r_paged.sched.max_occupancy \
        / max(r_cont.sched.max_occupancy, 1)
    emit(f"serving/{ARCH}/prefix_equal_bytes/concurrency_gain", gain,
         f"paged+cache={r_paged.sched.max_occupancy} "
         f"contiguous={r_cont.sched.max_occupancy} "
         f"hits={r_paged.stats.prefix_hits}/12 "
         f"(acceptance: > 3.5x at equal arena bytes — 7 shared prefix "
         f"blocks deduplicated, one private block per admission)")
    METRICS["prefix_shared_concurrency_gain"] = gain


def kv_quant_comparison(cfg, model, params) -> None:
    """ISSUE 8 acceptance: int8 quantized KV pages, in-kernel dequant.

    Part A holds the workload AND schedule fixed (same stream, realtime
    off) and serves it through two paged engines differing only in
    ``kv_quant``: the per-slot KV stream bytes/token must drop to
    <= 0.55x bf16. The exact factor is (head_dim + 2) / (2 * head_dim)
    = 0.53125 at the reduced head_dim of 32 — int8 codes plus one fp16
    scale per (position, kv-head) replace 2-byte elements — and the
    arena's per-block resident bytes shrink by the same factor. The
    quantized arena must not re-jit (dict-of-pages leaves change the
    pytree, not the traced slot/chunk geometry).

    Part B measures e2e greedy token agreement *teacher-forced*: the
    bf16 engine generates reference rollouts, then every next-token
    prediction is re-asked end-to-end through the int8 engine on the
    reference history. (Teacher forcing isolates per-step argmax
    fidelity from cascade divergence — a lossy comparison diverging at
    one near-tie would otherwise invalidate every later position.) The
    random-init surrogate model has near-tie logit margins a trained
    checkpoint does not — exact 0.0 top-2 gaps occur, which even two
    bf16 runs may break differently — so the gated rate counts
    margin-confident positions (reference top-2 logit gap > 0.02, about
    2x the largest quant-induced flip margin observed, leaving ~3/4 of
    positions in play) and the raw all-positions rate is reported
    alongside."""
    mk_eng = lambda kvq, ms, nb, slots: ServingEngine(
        model, params, num_slots=slots, max_seq=ms, chunk_size=8,
        block_size=4, num_blocks=nb, paged_attn="fused", kv_quant=kvq)

    runs = {}
    for kvq in ("none", "int8"):
        eng = mk_eng(kvq, PROMPT_MAX + GEN, 4 * 6, 4)
        reqs = make_requests(cfg, np.random.RandomState(19), n=8, lo=8)
        runs[kvq] = (eng, eng.serve(reqs, seed=0, realtime=False))
    kvpt = {k: r.ledger.kv_stream_bytes() / max(r.stats.decode_tokens, 1)
            for k, (e, r) in runs.items()}
    ratio = kvpt["int8"] / kvpt["none"]
    blk_ratio = runs["int8"][0].arena.block_bytes() \
        / runs["none"][0].arena.block_bytes()
    assert ratio <= 0.55, f"kv_stream ratio {ratio:.4f} > 0.55"
    assert runs["int8"][1].step_compiles == 1
    for k, (eng, r) in runs.items():
        emit(f"serving/{ARCH}/kv_{k}/kv_stream_bytes_per_token", kvpt[k],
             f"block_bytes={eng.arena.block_bytes()} "
             f"completed={r.sched.completed}/8 "
             f"step_compiles={r.step_compiles}")
    emit(f"serving/{ARCH}/kv_int8/kv_stream_ratio", ratio,
         f"block_bytes_ratio={blk_ratio:.5f} "
         f"(acceptance: <= 0.55x bf16 at equal live tokens; exact "
         f"factor (hd+2)/(2hd) at hd={cfg.resolved_head_dim()})")
    METRICS["kv_quant_stream_ratio"] = ratio
    METRICS["kv_quant_block_bytes_ratio"] = blk_ratio
    METRICS["kv_quant_step_compiles"] = runs["int8"][1].step_compiles

    GEN_TF = 24
    rng = np.random.RandomState(23)
    prompts = [rng.randint(0, cfg.vocab_size, 8) for _ in range(3)]
    mk = lambda toks, gen: [Request(rid=j, tokens=t, max_new_tokens=gen)
                            for j, t in enumerate(toks)]
    ref = mk_eng("none", 40, 3 * 10, 3).serve(
        mk(prompts, GEN_TF), seed=0, realtime=False)
    full = [np.concatenate([p, np.asarray(s.generated)])
            for p, s in zip(prompts, ref.sequences)]
    # Score every position's reference top-2 logit margin with the
    # sequential lockstep oracle (prefill + teacher-forced decode steps:
    # the logits that predicted token k live at step k-1).
    step = jax.jit(lambda p, t, pos, c: model.decode_step(p, t, pos, c))
    tf_prompts, targets, margins = [], [], []
    for p, f in zip(prompts, full):
        lp = len(p)
        _, cache0 = model.prefill(params,
                                  {"tokens": jnp.asarray(f[None, :lp - 1])})
        arena = KVArena(model, 1, 40)
        arena.write_prefill(cache0, 0)
        cache = arena.buffers
        for k in range(lp, len(f)):
            logits, cache = step(params,
                                 jnp.asarray([[int(f[k - 1])]], jnp.int32),
                                 jnp.asarray([k - 1], jnp.int32), cache)
            row = np.asarray(logits[0, -1], np.float32)
            top2 = np.sort(row)[-2:]
            tf_prompts.append(f[:k])
            targets.append(int(f[k]))
            margins.append(float(top2[1] - top2[0]))
    rq = mk_eng("int8", 40, 3 * 10, 3).serve(
        mk(tf_prompts, 1), seed=0, realtime=False)
    hit = np.array([int(s.generated[0]) == t
                    for s, t in zip(rq.sequences, targets)])
    conf = np.asarray(margins) > 0.02
    raw = float(hit.mean())
    agree = float(hit[conf].mean())
    assert agree >= 0.99, f"confident token agreement {agree:.4f} < 0.99"
    emit(f"serving/{ARCH}/kv_int8/token_agreement", agree,
         f"confident={int(hit[conf].sum())}/{int(conf.sum())} "
         f"raw={raw:.4f} ({int(hit.sum())}/{hit.size}) "
         f"(acceptance: >= 0.99 teacher-forced greedy agreement on "
         f"margin-confident positions)")
    METRICS["kv_quant_token_agreement"] = agree


_SHARDED_WORKER = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
from repro.configs.registry import get_config
from repro.models.api import build_model
from repro.runtime.engine import ServingEngine
from repro.runtime.request import Request

cfg = get_config("qwen3-0.6b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))


def requests():
    rng = np.random.RandomState(29)
    return [Request(rid=i, tokens=rng.randint(0, cfg.vocab_size,
                                              int(rng.randint(6, 13))),
                    max_new_tokens=8) for i in range(6)]


out = {}
for tp in (1, 2):
    mesh = None
    if tp > 1:
        devs = np.array(jax.devices()[:tp]).reshape(1, tp)
        mesh = jax.sharding.Mesh(devs, ("data", "model"))
    eng = ServingEngine(model, params, num_slots=4, max_seq=24,
                        chunk_size=8, block_size=4, num_blocks=23,
                        paged_attn="fused", mesh=mesh)
    rep = eng.serve(requests(), seed=0, realtime=False)
    led = rep.ledger
    out[f"tp{tp}"] = {
        "tokens": [[int(t) for t in s.generated] for s in rep.sequences],
        "compiles": rep.step_compiles,
        "bytes_per_token": led.bytes_per_token(),
        "weight_stream_per_token": led.weight_stream_bytes_per_token(),
        "per_device_weight_stream_per_token":
            led.per_device_weight_stream_bytes_per_token(),
    }
print("RESULT " + json.dumps(out))
"""


def sharded_tp_scaling() -> None:
    """Part 8: tensor-parallel serving through the unified chunked step.

    The mesh shards weight out-features over 'model', so each device
    streams 1/tp of every linear weight per step — the paper's dominant
    transfer term divides across the mesh while the *aggregate* ledger
    stays degree-invariant (same workload, same totals, same baselines).
    Runs in a subprocess because the mesh needs forced host devices
    (XLA_FLAGS must be set before jax import; the in-process benches
    need the real single CPU device), pinned to the CPU platform. Gates: token-identical outputs,
    one step compile, and per-device weight-stream bytes/token at TP=2
    <= 0.55x TP=1 (the exact factor is 0.5)."""
    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(_SHARDED_WORKER)
        worker = f.name
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    env.pop("XLA_FLAGS", None)
    # The worker checks modeled bytes on forced host devices: pin it to
    # the CPU so it never reaches for an accelerator this process holds.
    env["JAX_PLATFORMS"] = "cpu"
    try:
        proc = subprocess.run([sys.executable, worker], capture_output=True,
                              text=True, timeout=1800, env=env)
        assert proc.returncode == 0, proc.stderr[-4000:]
    finally:
        os.unlink(worker)
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    tp1, tp2 = res["tp1"], res["tp2"]
    assert tp2["tokens"] == tp1["tokens"], "TP=2 diverged from TP=1"
    assert tp1["compiles"] == 1 and tp2["compiles"] == 1
    # Aggregate cells are degree-invariant; the per-device stream halves.
    assert tp2["bytes_per_token"] == tp1["bytes_per_token"]
    assert tp2["weight_stream_per_token"] == tp1["weight_stream_per_token"]
    ratio = tp2["per_device_weight_stream_per_token"] \
        / tp1["per_device_weight_stream_per_token"]
    assert ratio <= 0.55, f"per-device weight-stream ratio {ratio} > 0.55"
    for tp in (1, 2):
        r = res[f"tp{tp}"]
        emit(f"serving/{ARCH}/sharded_tp{tp}/"
             f"per_device_weight_stream_bytes_per_token",
             r["per_device_weight_stream_per_token"],
             f"aggregate={r['weight_stream_per_token']:.1f} "
             f"step_compiles={r['compiles']}")
    emit(f"serving/{ARCH}/sharded_tp2/per_device_weight_stream_ratio",
         ratio,
         "(acceptance: <= 0.55x TP=1; exact 1/tp factor, outputs pinned "
         "token-identical in-bench, aggregate ledger degree-invariant)")
    METRICS["sharded_tp2_weight_stream_ratio"] = ratio
    METRICS["sharded_step_compiles"] = tp2["compiles"]
    METRICS["sharded_aggregate_bytes_ratio"] = \
        tp2["bytes_per_token"] / tp1["bytes_per_token"]


def telemetry_validation(cfg, model, params) -> None:
    """Part 9 (ISSUE 10 acceptance): per-step serving telemetry.

    The same paged stream is served twice, telemetry off and on, and the
    instrumented run is held to the observability contract: (a) outputs
    token-for-token identical and still ONE step compile — the timeline
    is strictly host-side; (b) the summed per-step ledger deltas close
    against ``TransferLedger.breakdown()`` as EXACT dict equality (the
    charge tap shares the ledger's per-charge fold order, so closure is
    bit-exact, not approximate); (c) the JSONL metrics sink and the
    Perfetto/Chrome trace export both pass their schema validators;
    (d) the streaming ``LogHistogram`` percentiles land within the
    geometric-bin error bound of the exact post-hoc quantiles."""
    from repro.runtime.telemetry import (validate_chrome_trace,
                                         validate_metrics_jsonl)
    mk = lambda: make_requests(cfg, np.random.RandomState(31), n=8, lo=6)
    mk_eng = lambda tel: ServingEngine(
        model, params, num_slots=4, max_seq=PROMPT_MAX + GEN,
        chunk_size=8, block_size=4, num_blocks=4 * 7, paged_attn="fused",
        telemetry=tel)
    r_off = mk_eng(False).serve(mk(), seed=0, realtime=False)
    r_on = mk_eng(True).serve(mk(), seed=0, realtime=False)
    identical = all(a.generated == b.generated for a, b in
                    zip(r_off.sequences, r_on.sequences))
    assert identical, "telemetry-on serve diverged from telemetry-off"
    tl = r_on.timeline
    closure = tl.ledger_delta_totals() == r_on.ledger.breakdown()
    assert closure, "per-step ledger deltas failed to close bit-exactly"
    assert r_on.step_compiles == 1

    with tempfile.TemporaryDirectory() as td:
        mpath = os.path.join(td, "metrics.jsonl")
        tpath = os.path.join(td, "trace.json")
        tl.write_metrics_jsonl(mpath)
        tl.write_chrome_trace(tpath)
        n_steps = validate_metrics_jsonl(mpath)
        n_spans = validate_chrome_trace(tpath)
    assert n_steps == len(tl.events)

    # Streaming-estimator accuracy: the histogram read must sit within
    # the geometric-bin error bound of the exact post-hoc quantile.
    lats = sorted(s.latency_s for s in r_on.sequences)
    exact_p50 = lats[max(-(-50 * len(lats) // 100) - 1, 0)]  # nearest rank
    est_p50 = tl.hists["request_latency_s"].percentile(50)
    rel_err = abs(est_p50 - exact_p50) / max(exact_p50, 1e-12)
    assert rel_err <= 0.12, f"hist p50 rel err {rel_err:.4f} > 0.12"

    emit(f"serving/{ARCH}/telemetry/ledger_closure", float(closure),
         f"steps={len(tl.events)} cells_delta_sum==breakdown (bit-exact) "
         f"jsonl_steps={n_steps} trace_spans={n_spans}")
    emit(f"serving/{ARCH}/telemetry/hist_p50_rel_err", rel_err,
         f"est={est_p50*1e3:.2f}ms exact={exact_p50*1e3:.2f}ms "
         f"(bound: geometric bin width, <= 0.12)")
    emit(f"serving/{ARCH}/telemetry/step_compiles", r_on.step_compiles,
         f"tokens_identical={int(identical)} "
         f"(acceptance: telemetry never perturbs the traced step)")
    METRICS["telemetry_step_compiles"] = r_on.step_compiles
    METRICS["telemetry_tokens_identical"] = float(identical)
    METRICS["telemetry_ledger_closure"] = float(closure)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduced", action="store_true",
                    help="reduced model config (always on: this benchmark "
                         "is CPU-sized by construction)")
    ap.add_argument("--json", default="",
                    help="write the regression-gate metrics JSON here")
    args = ap.parse_args()
    cfg = ASSIGNED[ARCH].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    occupancy_sweep(cfg, model, params)
    paging_comparison(cfg, model, params)
    chunked_comparison(cfg, model, params)
    paged_attn_scaling(cfg, model, params)
    speculative_amortization(cfg, model, params)
    prefix_sharing(cfg, model, params)
    kv_quant_comparison(cfg, model, params)
    sharded_tp_scaling()
    telemetry_validation(cfg, model, params)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"bench": "bench_serving", "arch": f"{ARCH}-reduced",
                       "metrics": METRICS}, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
