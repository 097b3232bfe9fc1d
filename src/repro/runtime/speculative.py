"""Speculative decoding: proposers, accept-rate control, draft plumbing.

The paper's system-level result (§V.A) is that host<->accelerator data
transfer — not kernel math — bounds decode on the CGLA, and the live
ledger reproduces it: the quantized *linear* weights stream once per
unified step, so weight-stream bytes per generated token is proportional
to steps-per-token. Speculative decoding attacks exactly that ratio:
propose k tokens, verify them all in ONE chunked step (the PR 3 unified
(slots, chunk) step already computes per-position logits for multi-token
feeds — it *is* a verifier), and every accepted token amortizes the
step's weight stream. The accelerator-systems surveys the ROADMAP tracks
(Kachris 2024; Li et al. 2024) both name speculative execution as a
first-class lever for memory-bound decode.

Two proposers behind one duck-typed interface (``propose`` is the only
method the engine requires per step; lifecycle hooks are optional):

* ``NGramProposer`` — model-free prompt-lookup drafting: match the
  longest recent n-gram of a sequence's context (prompt + generated)
  against an earlier occurrence and propose its continuation. Free to
  run (no second model, no extra transfers), wins on repetitive
  suffixes, and runs in CI with no second checkpoint.
* ``DraftModelProposer`` — a small draft model (e.g. qwen3-0.6b drafting
  for qwen3-8b) running greedy chunked decode over its OWN slot arena,
  mirroring the target's slot axis, with its OWN transfer ledger account
  so the draft's weight stream is measured against the amortization win
  rather than hidden.

Both proposers are *deterministic* (point-mass draft distributions), so
the verification head (``sampling.verify_slots``) preserves the target
distribution exactly: greedy slots accept on argmax match; stochastic
slots accept x̂ w.p. q(x̂) and sample the leftover on rejection.

``SpecController`` adapts the per-slot speculation depth from an
accept-rate EMA — proposing deep against a low-accept stream wastes
chunk lanes and rollback work — and the scheduler additionally trims
speculative lanes under token-budget pressure (``plan_feeds`` funds
decode and prefill before speculation, so a loaded engine degrades to
plain decode instead of starving admissions).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import interpret_kernels
from repro.parallel import sharding as shard_rules
from repro.runtime.kvcache import KVArena
from repro.runtime.request import Sequence
from repro.runtime.transfers import TransferLedger

SPEC_MODES = ("off", "ngram", "draft")
# Families whose decode state is not purely seq-indexed KV: an SSM/conv
# recurrence advanced by a rejected token cannot be rolled back without
# recomputation, so speculation refuses them up front.
RECURRENT_FAMILIES = ("ssm", "hybrid")
# Families whose decode is conditioned on per-request modality state
# (encoder frames, vision embeds) a text-only draft pass cannot supply —
# a draft from one of these would propose from zeroed cross state.
CONDITIONED_FAMILIES = ("encdec", "vlm")


class NGramProposer:
    """Model-free prompt-lookup drafting.

    Find the longest n-gram (``max_n`` down to ``min_n``) ending the
    context that also occurs earlier, and propose the k tokens that
    followed its most recent earlier occurrence. No device work, no
    transfers, no second checkpoint — the CI-default proposer."""

    def __init__(self, max_n: int = 3, min_n: int = 1):
        if not 1 <= min_n <= max_n:
            raise ValueError(f"bad n-gram range [{min_n}, {max_n}]")
        self.max_n = max_n
        self.min_n = min_n

    def propose(self, seqs: Dict[int, Sequence],
                grants: Dict[int, int]) -> Dict[int, np.ndarray]:
        """Per-slot proposals (possibly empty) for the granted depths."""
        out = {}
        for slot, k in grants.items():
            out[slot] = self._propose_one(seqs[slot].context_tokens(), k)
        return out

    def _propose_one(self, ctx: np.ndarray, k: int) -> np.ndarray:
        none = np.zeros((0,), np.int32)
        if k <= 0 or len(ctx) < self.min_n + 1:
            return none
        for n in range(min(self.max_n, len(ctx) - 1), self.min_n - 1, -1):
            gram = ctx[-n:]
            # windows[i] == ctx[i:i+n]; exclude the final (query) window.
            windows = np.lib.stride_tricks.sliding_window_view(ctx, n)[:-1]
            hits = np.flatnonzero((windows == gram).all(axis=1))
            if hits.size == 0:
                continue
            start = int(hits[-1]) + n          # most recent continuation
            cont = ctx[start:start + k]
            if cont.size:
                return cont.astype(np.int32)
        return none


@dataclasses.dataclass
class SpecController:
    """Per-slot speculation-depth controller.

    Tracks an accept-rate EMA per slot and scales the proposal depth
    between 1 and ``k_max``: a stream that stops accepting decays to
    shallow (cheap) speculation, a stream on a roll climbs back. Fresh
    admissions start optimistic (full depth) — the first verification
    corrects them. The *budget* dimension of adaptivity lives in
    ``Scheduler.plan_feeds``, which funds speculative lanes last."""
    k_max: int
    decay: float = 0.7          # EMA weight on history
    adaptive: bool = True
    ema: Dict[int, float] = dataclasses.field(default_factory=dict)

    def reset(self, slot: int) -> None:
        """Forget ``slot``'s history (slot reuse by a new sequence)."""
        self.ema.pop(slot, None)

    def depth(self, slot: int) -> int:
        """Proposal depth to request for ``slot`` this step."""
        if not self.adaptive:
            return self.k_max
        e = self.ema.get(slot, 1.0)
        return max(1, min(self.k_max, round(e * self.k_max)))

    def update(self, slot: int, proposed: int, accepted: int) -> None:
        """Fold one verification outcome into ``slot``'s accept EMA."""
        if proposed <= 0:
            return
        rate = accepted / proposed
        self.ema[slot] = self.decay * self.ema.get(slot, 1.0) \
            + (1.0 - self.decay) * rate


class DraftModelProposer:
    """Small-model drafting over a mirrored slot arena.

    The draft model runs greedy chunked decode on its own contiguous
    ``KVArena`` sized like the target's slot axis. Per engine step it
    (1) streams each speculating slot's newly committed tokens into the
    draft cache (catch-up), (2) rolls autoregressively k tokens forward,
    then (3) rewinds its cache depth to the verified prefix next round
    (rejected draft KV is masked stale state, rewritten before any read —
    the *target* arena is the one held to the bit-identical rollback
    contract). The catch-up feed and ALL k greedy rolls run in ONE
    jitted dispatch: a chunked pass whose emitted token seeds a
    ``lax.scan`` of single-token feedback passes — one host->device
    round trip per proposal round instead of 1 + (k-1). All draft
    transfers are charged to ``self.ledger`` — a separate account, so
    bench/serve reports show the draft's weight stream alongside the
    amortization it buys. With ``mesh`` set, the draft shards over the
    *same* serving mesh as the target (params out-feature over 'model',
    slot axis over 'data')."""

    def __init__(self, model, params, *, num_slots: int, max_seq: int,
                 chunk: int, quant: str = "none", impl: str = "ref",
                 cache_dtype=jnp.bfloat16, mesh=None):
        if model.cfg.family in RECURRENT_FAMILIES:
            raise ValueError(
                f"draft model family {model.cfg.family!r} is recurrent — "
                "its state cannot be rolled back after rejection")
        if model.cfg.family in CONDITIONED_FAMILIES:
            raise ValueError(
                f"draft model family {model.cfg.family!r} needs "
                "per-request conditioning (encoder frames / vision "
                "embeds) the proposer cannot provide — it would draft "
                "from zeroed cross state; use a decoder-only draft")
        self.model = model
        self.mesh = mesh
        self.dp, self.tp = shard_rules.serving_degrees(mesh)
        self.params = params if mesh is None else jax.device_put(
            params, shard_rules.serving_param_shardings(params, mesh))
        self.num_slots = num_slots
        self.chunk = max(2, chunk)
        self.quant = quant
        self.arena = KVArena(model, num_slots, max_seq, dtype=cache_dtype,
                             mesh=mesh)
        self.ledger = TransferLedger(model.cfg, quant, dp=self.dp,
                                     tp=self.tp)
        self.steps = 0
        # Committed context length the draft has verified-and-ingested,
        # and the speculative tail (proposal tokens already in its cache).
        self._depth = [0] * num_slots
        self._tail: List[List[int]] = [[] for _ in range(num_slots)]

        kw = dict(quant=quant, impl=impl, interpret=interpret_kernels())

        def pin_cache(arena):
            if mesh is None or self.arena._shardings is None:
                return arena
            return jax.tree.map(jax.lax.with_sharding_constraint,
                                arena, self.arena._shardings)

        def greedy(logits_last, active):
            return jnp.where(active, jnp.argmax(logits_last, axis=-1)
                             .astype(jnp.int32), 0)

        def droll(p, tokens, pos0, lengths, active, arena, rolls):
            """One dispatch per proposal round: chunked catch-up feed,
            whose final-position argmax is proposal 1, then ``rolls``
            single-token greedy feedback passes under ``lax.scan`` —
            proposals 2..k with zero extra dispatches. ``rolls`` is
            static (one compilation per distinct depth, bounded by
            chunk-1). Lanes needing fewer rolls keep rolling; their
            surplus tokens are dropped on the host and their surplus KV
            writes land past the tracked tail, where the next round's
            feed rewrites them before any read."""
            logits, arena = model.decode_step(p, tokens, pos0, arena,
                                              lengths=lengths, **kw)
            arena = pin_cache(arena)
            idx = jnp.maximum(lengths - 1, 0)
            last = jnp.take_along_axis(
                logits, idx[:, None, None], axis=1)[:, 0]
            nxt = greedy(last, active)
            if rolls == 0:
                return nxt[:, None], arena

            feed_len = active.astype(jnp.int32)   # inactive lanes write 0
            def body(carry, _):
                tok, pos, arena = carry
                lg, arena = model.decode_step(p, tok[:, None], pos, arena,
                                              lengths=feed_len, **kw)
                arena = pin_cache(arena)
                nx = greedy(lg[:, 0], active)
                return (nx, pos + 1, arena), nx
            (_, _, arena), rolled = jax.lax.scan(
                body, (nxt, pos0 + lengths, arena), None, length=rolls)
            props = jnp.concatenate([nxt[:, None], rolled.T], axis=1)
            return props, arena
        self._roll = jax.jit(droll, static_argnums=(6,),
                             donate_argnums=(5,))

    # -- lifecycle hooks -------------------------------------------------
    def reset_run(self) -> None:
        """Fresh ledger + slot state for a new serve() run (the draft's
        jitted step and arena storage stay warm — compilations are not
        repaid, mirroring ``ServingEngine.reset``).

        The engine calls this BEFORE constructing the run's telemetry
        ``StepTimeline``, which then attaches its charge tap to this
        fresh ledger — so per-step ``draft_delta`` cells in the timeline
        close bit-exactly against this account's ``breakdown()``, same
        contract as the main ledger."""
        self.ledger = TransferLedger(self.model.cfg, self.quant,
                                     dp=self.dp, tp=self.tp)
        self.steps = 0
        self._depth = [0] * self.num_slots
        self._tail = [[] for _ in range(self.num_slots)]

    def reset_slot(self, slot: int) -> None:
        """Target admission reused this slot: drop the previous
        occupant's draft state (stale KV is masked; constant leaves are
        zeroed just like the target arena's chunked admission)."""
        self._depth[slot] = 0
        self._tail[slot] = []
        self.arena.reset_slot(slot)

    # -- proposal --------------------------------------------------------
    def _sync(self, slot: int, ctx: np.ndarray) -> None:
        """Reconcile the draft cache with the committed context: the
        accepted proposal prefix stays (it equals what the target
        committed), the rejected tail is rewound (depth rollback — the
        stale KV is rewritten before any read)."""
        depth, tail = self._depth[slot], self._tail[slot]
        keep = 0
        while keep < len(tail) and depth + keep < len(ctx) \
                and tail[keep] == int(ctx[depth + keep]):
            keep += 1
        self._depth[slot] = depth + keep
        self._tail[slot] = []

    def _dispatch(self, tokens, pos0, lens, active, rolls: int):
        """Run one jitted draft dispatch (chunked feed + ``rolls`` scan
        passes); returns the (num_slots, 1 + rolls) proposal matrix on
        the host. Sharding comes from the committed inputs, plus the
        MoE replication pin the activation scope enables (see
        parallel/sharding.py)."""
        if self.mesh is None:
            put = jnp.asarray
        else:
            def put(a):
                a = np.asarray(a)
                return jax.device_put(
                    a, shard_rules.slot_sharding(self.mesh, a.ndim))
        with shard_rules.activation_mesh(self.mesh):
            props, self.arena.buffers = self._roll(
                self.params, put(tokens), put(pos0), put(lens),
                put(active), self.arena.buffers, rolls)
        self.steps += 1
        return np.asarray(props)

    def propose(self, seqs: Dict[int, Sequence],
                grants: Dict[int, int]) -> Dict[int, np.ndarray]:
        """Batched drafting, ONE dispatch per round: every speculating
        slot's catch-up tokens ride a chunked feed whose final logits
        emit proposal 1, and the jitted ``lax.scan`` rolls the remaining
        proposals without returning to the host. (Only a sequence whose
        committed backlog exceeds a whole chunk — preemption re-admission
        — pays extra catch-up dispatches first.)"""
        ctxs = {s: seqs[s].context_tokens() for s in grants}
        for slot, ctx in ctxs.items():
            self._sync(slot, ctx)
        # Per-lane committed catch-up queues. The final token of each
        # queue is consumed by the proposal dispatch itself (its logits
        # seed the roll), so phase-1 chunked catch-up always leaves at
        # least one token pending.
        pending = {s: [int(t) for t in ctxs[s][self._depth[s]:]]
                   for s in grants}
        while any(len(p) > self.chunk for p in pending.values()):
            tokens = np.zeros((self.num_slots, self.chunk), np.int32)
            pos0 = np.zeros((self.num_slots,), np.int32)
            lens = np.zeros((self.num_slots,), np.int32)
            active = np.zeros((self.num_slots,), bool)
            for s in grants:
                n = min(len(pending[s]) - 1, self.chunk)
                if n <= 0:
                    continue
                tokens[s, :n] = pending[s][:n]
                pending[s] = pending[s][n:]
                pos0[s] = self._depth[s]
                lens[s] = n
                active[s] = True
                self._depth[s] += n
            self._dispatch(tokens, pos0, lens, active, 0)
            self.ledger.charge_step_weights()         # shared linear pass
            for s in grants:
                if lens[s]:
                    self.ledger.charge_chunk("decode", int(lens[s]),
                                             int(pos0[s]) + int(lens[s]))
        # The proposal dispatch: feed the rest of every lane's committed
        # tokens, roll max-needed extra passes in-dispatch. Lanes wanting
        # fewer proposals than the deepest lane drop their surplus here.
        rolls = max(grants.values()) - 1
        tokens = np.zeros((self.num_slots, self.chunk), np.int32)
        pos0 = np.zeros((self.num_slots,), np.int32)
        lens = np.zeros((self.num_slots,), np.int32)
        active = np.zeros((self.num_slots,), bool)
        for s in grants:
            n = len(pending[s])
            tokens[s, :n] = pending[s]
            pos0[s] = self._depth[s]
            lens[s] = n
            active[s] = n > 0
            self._depth[s] += n
        props_mat = self._dispatch(tokens, pos0, lens, active, rolls)
        # Ledger: the dispatch runs 1 + rolls model passes, each
        # streaming the shared linear weights once; per-lane activation
        # chunks are charged only while that lane still needs proposals
        # (its surplus rolls move no host bytes — the whole matrix comes
        # back in one drain, charged per proposal below).
        self.ledger.charge_step_weights()
        props: Dict[int, np.ndarray] = {}
        for s in grants:
            if not active[s]:
                props[s] = np.zeros((0,), np.int32)
                continue
            k = grants[s]
            self.ledger.charge_chunk("decode", int(lens[s]),
                                     int(pos0[s]) + int(lens[s]))
            lane = props_mat[s, :k].astype(np.int32)
            props[s] = lane
            # Fed-back proposals (all but the last) extend the tracked
            # tail: they are real cache contents the next _sync matches
            # against the target's commit.
            self._tail[s].extend(int(t) for t in lane[:-1])
            for _ in range(k):
                self.ledger.charge_sampled()          # proposal drained d2h
        for i in range(rolls):
            self.ledger.charge_step_weights()
            for s in grants:
                if active[s] and i < grants[s] - 1:
                    p = int(pos0[s]) + int(lens[s]) + i
                    self.ledger.charge_chunk("decode", 1, p + 1)
        return props


def make_proposer(mode: str, *, draft_model=None, draft_params=None,
                  num_slots: int = 0, max_seq: int = 0, chunk: int = 0,
                  quant: str = "none", impl: str = "ref",
                  cache_dtype=jnp.bfloat16, mesh=None):
    """Build the proposer for ``mode`` ("ngram" or "draft")."""
    if mode == "ngram":
        return NGramProposer()
    if mode == "draft":
        if draft_model is None or draft_params is None:
            raise ValueError("spec='draft' needs spec_draft_model and "
                             "spec_draft_params")
        return DraftModelProposer(draft_model, draft_params,
                                  num_slots=num_slots, max_seq=max_seq,
                                  chunk=chunk, quant=quant, impl=impl,
                                  cache_dtype=cache_dtype, mesh=mesh)
    raise ValueError(f"unknown spec mode {mode!r} (choose from "
                     f"{SPEC_MODES})")


__all__ = ["NGramProposer", "DraftModelProposer", "SpecController",
           "make_proposer", "SPEC_MODES",
           "RECURRENT_FAMILIES"]
