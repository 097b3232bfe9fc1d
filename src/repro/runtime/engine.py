"""Serving engine: continuous-batching execution over a slot-based KV arena.

Layered runtime (paper §III.A transplanted to TPU/JAX, grown into a
scheduler/executor/cache-manager stack):

  * `runtime/request.py`   — request/sequence state machine
  * `runtime/scheduler.py` — FCFS admission + token-budget chunk planning
  * `runtime/kvcache.py`   — cache manager: contiguous slot arena, or the
                             paged block-table arena (``block_size`` set)
  * `runtime/transfers.py` — host<->device byte ledger (paper §V.A: data
                             transfer, not kernels, is the bottleneck)
  * this file              — the step executor: ONE jitted step over
                             (params, (slots, chunk) token batch, base
                             positions, valid lengths, active-mask,
                             arena[, block-tables]) with fused masked
                             per-slot sampling

Unified chunked prefill (the only prefill path — the legacy bucketed
pass was retired after its one release of overlap): there is NO separate
prefill phase. Prompt tokens stream through the *same* jitted step as
decode, up to ``chunk_size`` tokens per slot per iteration, so a single
traced shape (slots, chunk) covers admission, prompt ingestion and
generation — zero re-jits and zero pow2 padding. A slot ingesting its
prompt feeds `min(remaining, chunk)` tokens with sampling masked off; the
step that consumes the final prompt token samples the first generated
token from that token's logits (index ``lengths-1``), and the slot then
feeds one sampled token per step (``lengths == 1``). The transfer ledger
charges prompt bytes per chunk actually transferred — no pow2 bucket
waste — and the quantized linear weights stream once per *step* (all
slots share the pass), not once per slot. ``ModelAPI.prefill`` remains
only for the lockstep/eval entry points (launch.dryrun, trainer eval,
test oracles) — the serving runtime never calls it.

Speculative decoding (``spec={"ngram","draft"}``): the unified step
doubles as the *verifier*. A decoding slot feeds its committed token
plus up to k proposed tokens (model-free prompt-lookup n-grams, or a
small draft model with its own arena and ledger account — see
``runtime/speculative.py``); ``sampling.verify_slots`` reads every fed
position's logits to compute per-slot accept lengths (exact argmax
match when greedy, distribution-preserving rejection/leftover sampling
at temperature > 0) and the one token emitted past the accepted prefix.
Accepted tokens amortize the step's shared linear-weight stream — the
paper's dominant transfer term — and the rejected suffix is rolled back
in place (KV positions zeroed, paged block-table tails trimmed, no
recompute). Proposal lanes are funded from *leftover* step-token budget
and shrink with a per-slot accept-rate EMA, so a loaded engine degrades
to plain decode instead of starving prefill. Recurrent families
(ssm/hybrid) are refused up front: their state cannot roll back.

Paged mode: admission needs a free slot AND the first *chunk's* block
reservation (reservation then follows chunk progress); each step reserves
blocks covering every active slot's next feed; on allocator exhaustion
the youngest sequence is preempted back to the queue (recompute). The
block tables ride into the jitted step as a (num_slots, max_blocks) int32
argument, so mid-flight allocation never changes a traced shape. Inside
the step, paged K/V is attended by the fused block-table Pallas kernel
(``paged_attn="fused"``, the default — per-step KV traffic O(live
tokens)) or the legacy dense-gather oracle (``"ref"``, O(arena)).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import convert
from repro.kernels import common as kernel_common
from repro.models.api import ModelAPI
from repro.parallel import sharding as shard_rules
from repro.runtime import sampling, speculative
from repro.runtime.kvcache import KV_QUANT_MODES, KVArena, PagedKVArena
from repro.runtime.request import Request, SamplingParams, SeqState, Sequence
from repro.runtime.scheduler import Scheduler, SchedulerStats
from repro.runtime.telemetry import StepTimeline, span
from repro.runtime.transfers import TransferLedger, TransferReport


@dataclasses.dataclass
class SpecCounters:
    """Speculative-decoding tallies: proposal lanes fed to the verifier,
    lanes accepted by verification, and rejected KV positions rolled
    back (zeroed + block-trimmed)."""

    proposed: int = 0
    accepted: int = 0
    rolled_back: int = 0

    def to_dict(self) -> Dict[str, int]:
        """Plain-dict view (telemetry counters / bench emission)."""
        return dataclasses.asdict(self)


@dataclasses.dataclass
class PrefixCounters:
    """Prefix-sharing tallies: admissions that mapped a cached prefix,
    prompt positions satisfied from shared pages (never streamed or
    computed), and copy-on-write block splits taken before a write."""

    hits: int = 0
    hit_tokens: int = 0
    cow_splits: int = 0

    def to_dict(self) -> Dict[str, int]:
        """Plain-dict view (telemetry counters / bench emission)."""
        return dataclasses.asdict(self)


@dataclasses.dataclass
class PagedReadCounters:
    """Paged decode attention KV *read* traffic, accumulated per step
    from the engine's real tables/positions (same modeled-from-real-
    schedule philosophy as the transfer ledger): the fused kernel
    fetches each slot's live blocks (clamped index map — O(live
    tokens)); the ref gather materializes every slot's full-table-width
    view (O(arena)). ``read_bytes_per_device`` is the busiest 'data'
    replica's share under a serving mesh — each replica walks only its
    own slots' tables, so it is the max over replicas, not total/dp
    (equal to the total when dp == 1); the DP split only, the 'model'
    split of GQA pages is a further /tp not modeled here."""

    read_bytes: float = 0.0
    read_bytes_per_device: float = 0.0

    def to_dict(self) -> Dict[str, float]:
        """Plain-dict view (telemetry counters / bench emission)."""
        return dataclasses.asdict(self)


@dataclasses.dataclass
class GenStats:
    """Aggregate counters for one generation/serve run: timing and token
    counts inline, per-feature tallies grouped into documented
    sub-structs (``spec``, ``prefix``, ``paged``) with the historical
    flat names kept as read-write property aliases."""

    prefill_s: float = 0.0
    decode_s: float = 0.0
    tokens_in: int = 0              # prompt tokens per sequence
    tokens_out: int = 0             # generated tokens per sequence
    prefill_tokens: int = 0         # prompt tokens streamed (all L)
    decode_tokens: int = 0          # tokens emitted by decode steps
    cache_bytes: int = 0
    peak_resident_bytes: float = 0.0    # max arena bytes pinned by live seqs
    resident_bytes_sum: float = 0.0     # per-step resident-bytes accumulator
    live_tokens_sum: int = 0            # per-step live-cache-token accumulator
    steps: int = 0                  # unified steps executed
    spec: SpecCounters = dataclasses.field(default_factory=SpecCounters)
    prefix: PrefixCounters = dataclasses.field(
        default_factory=PrefixCounters)
    paged: PagedReadCounters = dataclasses.field(
        default_factory=PagedReadCounters)
    transfers: Optional[TransferReport] = None
    draft_transfers: Optional[TransferReport] = None  # spec="draft" account

    # -- legacy flat aliases (pre-grouping attribute names) --------------
    @property
    def spec_proposed(self) -> int:
        """Alias of ``spec.proposed`` (historical flat name)."""
        return self.spec.proposed

    @spec_proposed.setter
    def spec_proposed(self, v: int) -> None:
        """Write through to ``spec.proposed``."""
        self.spec.proposed = v

    @property
    def spec_accepted(self) -> int:
        """Alias of ``spec.accepted`` (historical flat name)."""
        return self.spec.accepted

    @spec_accepted.setter
    def spec_accepted(self, v: int) -> None:
        """Write through to ``spec.accepted``."""
        self.spec.accepted = v

    @property
    def spec_rolled_back(self) -> int:
        """Alias of ``spec.rolled_back`` (historical flat name)."""
        return self.spec.rolled_back

    @spec_rolled_back.setter
    def spec_rolled_back(self, v: int) -> None:
        """Write through to ``spec.rolled_back``."""
        self.spec.rolled_back = v

    @property
    def prefix_hits(self) -> int:
        """Alias of ``prefix.hits`` (historical flat name)."""
        return self.prefix.hits

    @prefix_hits.setter
    def prefix_hits(self, v: int) -> None:
        """Write through to ``prefix.hits``."""
        self.prefix.hits = v

    @property
    def prefix_hit_tokens(self) -> int:
        """Alias of ``prefix.hit_tokens`` (historical flat name)."""
        return self.prefix.hit_tokens

    @prefix_hit_tokens.setter
    def prefix_hit_tokens(self, v: int) -> None:
        """Write through to ``prefix.hit_tokens``."""
        self.prefix.hit_tokens = v

    @property
    def cow_splits(self) -> int:
        """Alias of ``prefix.cow_splits`` (historical flat name)."""
        return self.prefix.cow_splits

    @cow_splits.setter
    def cow_splits(self, v: int) -> None:
        """Write through to ``prefix.cow_splits``."""
        self.prefix.cow_splits = v

    @property
    def paged_kv_read_bytes(self) -> float:
        """Alias of ``paged.read_bytes`` (historical flat name)."""
        return self.paged.read_bytes

    @paged_kv_read_bytes.setter
    def paged_kv_read_bytes(self, v: float) -> None:
        """Write through to ``paged.read_bytes``."""
        self.paged.read_bytes = v

    @property
    def paged_kv_read_bytes_per_device(self) -> float:
        """Alias of ``paged.read_bytes_per_device`` (historical flat
        name)."""
        return self.paged.read_bytes_per_device

    @paged_kv_read_bytes_per_device.setter
    def paged_kv_read_bytes_per_device(self, v: float) -> None:
        """Write through to ``paged.read_bytes_per_device``."""
        self.paged.read_bytes_per_device = v

    def to_dict(self) -> Dict:
        """Scalar counter snapshot: timing/token fields inline plus the
        grouped sub-structs under their group keys — the shared shape
        telemetry counters and bench emission read from (transfer
        reports are separate frozen views, not repeated here)."""
        return {
            "prefill_s": self.prefill_s, "decode_s": self.decode_s,
            "tokens_in": self.tokens_in, "tokens_out": self.tokens_out,
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.decode_tokens,
            "cache_bytes": self.cache_bytes,
            "peak_resident_bytes": self.peak_resident_bytes,
            "steps": self.steps,
            "spec": self.spec.to_dict(),
            "prefix": self.prefix.to_dict(),
            "paged": self.paged.to_dict(),
        }

    @property
    def steps_per_token(self) -> float:
        """Unified steps per generated token — the transfer-amortization
        ratio: the linear weight stream flows once per step, so accepted
        speculative tokens push this (and weight-stream bytes/token)
        below the 1-step-per-token floor of plain decode."""
        return self.steps / self.decode_tokens if self.decode_tokens else 0.0

    @property
    def spec_accept_rate(self) -> float:
        """Accepted fraction of proposed speculative tokens."""
        return self.spec_accepted / self.spec_proposed \
            if self.spec_proposed else 0.0

    @property
    def resident_bytes_per_token(self) -> float:
        """Mean arena bytes *reserved* per live cache token over the run —
        the paging win: the slot arena pins max_seq per sequence, the
        paged arena pins ceil(len/block)*block."""
        return self.resident_bytes_sum / self.live_tokens_sum \
            if self.live_tokens_sum else 0.0

    @property
    def e2e_s(self) -> float:
        """Total wall time (prefill + decode phases)."""
        return self.prefill_s + self.decode_s

    @property
    def decode_tok_per_s(self) -> float:
        """Decode-phase throughput: only decode-emitted tokens over
        decode-phase wall time (no prefill-derived token leaks in)."""
        n = self.decode_tokens or self.tokens_out
        return n / self.decode_s if self.decode_s else 0.0

    @property
    def prefill_tok_per_s(self) -> float:
        """Prefill-phase throughput (prompt tokens per second)."""
        n = self.prefill_tokens or self.tokens_in
        return n / self.prefill_s if self.prefill_s else 0.0


@dataclasses.dataclass
class ServeReport:
    """Everything a finished serve run reports: aggregate stats, the
    per-sequence records, scheduler stats and the compile count."""

    stats: GenStats                 # stats.transfers: frozen ledger view
    sequences: List[Sequence]       # finished, submission order
    sched: SchedulerStats
    step_compiles: int              # decode-step compilations (1 == no re-jit)
    ledger: Optional[TransferLedger] = None   # live ledger (summary_lines)
    # Telemetry StepTimeline (engine telemetry=True): per-step events,
    # streaming latency histograms, trace/metrics exporters.
    timeline: Optional[object] = None

    @property
    def transfers(self) -> TransferReport:
        """Frozen transfer-ledger view (see docs/transfer-ledger.md)."""
        return self.stats.transfers

    def latency_percentiles(self, qs=(50, 90, 99)) -> Dict[int, float]:
        """Request-latency percentiles (seconds) over finished sequences."""
        lats = [s.latency_s for s in self.sequences if s.latency_s is not None]
        if not lats:
            return {q: 0.0 for q in qs}
        return {q: float(np.percentile(lats, q)) for q in qs}

    @property
    def throughput_tok_s(self) -> float:
        """Generated tokens per second of total wall time."""
        return self.stats.decode_tokens / self.stats.e2e_s \
            if self.stats.e2e_s else 0.0


class ServingEngine:
    """Continuous-batching executor over a fixed-slot KV arena."""

    def __init__(self, model: ModelAPI, params, *, quant: str = "none",
                 num_slots: int = 4, max_seq: int = 2048, impl: str = "ref",
                 chunk_size: int = 8,
                 step_token_budget: Optional[int] = None,
                 top_k: int = 0, top_p: float = 1.0,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 paged_attn: str = "fused",
                 spec: str = "off", spec_k: int = 4,
                 spec_adaptive: bool = True,
                 spec_draft_model: Optional[ModelAPI] = None,
                 spec_draft_params=None,
                 prefix_cache: bool = False,
                 kv_quant: str = "none",
                 mesh=None,
                 offload_decisions: Optional[Dict[str, bool]] = None,
                 host_sampling: bool = False, donate_cache: bool = True,
                 telemetry: bool = False,
                 cache_dtype=jnp.bfloat16):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if paged_attn not in ("fused", "ref"):
            raise ValueError(f"unknown paged_attn {paged_attn!r}")
        if spec not in speculative.SPEC_MODES:
            raise ValueError(f"unknown spec mode {spec!r} (choose from "
                             f"{speculative.SPEC_MODES})")
        if spec != "off":
            if model.cfg.family in speculative.RECURRENT_FAMILIES:
                raise ValueError(
                    f"speculative decoding is unsupported for the "
                    f"{model.cfg.family!r} family: rejected tokens have "
                    "advanced the recurrent state, which cannot be rolled "
                    "back without recomputation")
            if chunk_size < 2:
                raise ValueError("speculative decoding needs chunk_size "
                                 ">= 2 (one committed-token lane plus at "
                                 "least one proposal lane)")
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if spec == "draft":
            if spec_draft_model is None or spec_draft_params is None:
                raise ValueError("spec='draft' requires spec_draft_model "
                                 "and spec_draft_params")
            if spec_draft_model.cfg.vocab_size != model.cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {spec_draft_model.cfg.vocab_size} != "
                    f"target vocab {model.cfg.vocab_size}: proposal ids "
                    "would not be target token ids")
            if model.cfg.family == "encdec":
                raise ValueError("spec='draft' supports decoder-only "
                                 "families (the draft has no encoder "
                                 "frames to condition on)")
        if prefix_cache:
            if block_size is None:
                raise ValueError("prefix_cache requires the paged arena "
                                 "(set block_size)")
            if model.cfg.family in speculative.RECURRENT_FAMILIES:
                raise ValueError(
                    f"prefix_cache is unsupported for the "
                    f"{model.cfg.family!r} family: recurrent state is not "
                    "addressable by token-block chains")
            if model.cfg.family in ("encdec", "vlm"):
                raise ValueError(
                    f"prefix_cache is unsupported for the "
                    f"{model.cfg.family!r} family: prompt KV depends on "
                    "per-request conditioning (encoder frames / vision "
                    "embeddings), so equal token chains do not imply "
                    "equal pages")
        if kv_quant not in KV_QUANT_MODES:
            raise ValueError(f"unknown kv_quant mode {kv_quant!r} "
                             f"(choose from {KV_QUANT_MODES})")
        if kv_quant != "none":
            if block_size is None:
                raise ValueError(
                    "kv_quant requires the paged arena (set block_size): "
                    "quantize-on-insert and in-kernel dequant live on the "
                    "paged block-table path; the contiguous slot arena "
                    "has no quantized read path")
            if model.cfg.family in speculative.RECURRENT_FAMILIES:
                raise ValueError(
                    f"kv_quant is unsupported for the "
                    f"{model.cfg.family!r} family: recurrent state is a "
                    "running summary, not per-position KV pages — "
                    "requantizing it every step would compound rounding "
                    "error across the whole sequence")
            if model.cfg.family == "encdec":
                raise ValueError(
                    "kv_quant is unsupported for the 'encdec' family: "
                    "cross-attention KV is written by the one-time "
                    "encoder pass (write_prefill), which bypasses the "
                    "quantize-on-insert path")
        self.mesh = mesh
        self.dp, self.tp = shard_rules.serving_degrees(mesh)
        if mesh is not None:
            shard_rules.validate_serving_mesh(
                mesh, num_heads=model.cfg.num_heads,
                num_kv_heads=model.cfg.num_kv_heads,
                vocab_size=model.cfg.vocab_size, num_slots=num_slots)
            if spec == "draft":
                # The draft model shards over the *same* mesh (its params
                # and context pass run under the engine's activation
                # rules), so it must satisfy the same divisibility.
                shard_rules.validate_serving_mesh(
                    mesh, num_heads=spec_draft_model.cfg.num_heads,
                    num_kv_heads=spec_draft_model.cfg.num_kv_heads,
                    vocab_size=spec_draft_model.cfg.vocab_size,
                    num_slots=num_slots)
        self.model = model
        self.params = params if mesh is None else jax.device_put(
            params, shard_rules.serving_param_shardings(params, mesh))
        self.quant = quant
        self.kv_quant = kv_quant
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.impl = impl
        self.chunk_size = max(1, min(chunk_size, max_seq))
        self.step_token_budget = step_token_budget
        # Engine-level defaults, used when a request leaves them unset
        # (sampling configs are per-slot *data* in the jitted step, so
        # mixed streams share one compilation).
        self.top_k, self.top_p = top_k, top_p
        self.paged = block_size is not None
        self.paged_attn = paged_attn
        self.cache_dtype = cache_dtype
        self.spec = spec
        self.spec_k = min(spec_k, self.chunk_size - 1) if spec != "off" \
            else 0
        self._spec_ctrl = speculative.SpecController(
            k_max=self.spec_k, adaptive=spec_adaptive) \
            if spec != "off" else None
        self._proposer = speculative.make_proposer(
            spec, draft_model=spec_draft_model,
            draft_params=spec_draft_params, num_slots=num_slots,
            max_seq=max_seq, chunk=self.chunk_size, quant=quant, impl=impl,
            cache_dtype=cache_dtype, mesh=mesh) if spec != "off" else None
        self._block_size, self._num_blocks = block_size, num_blocks
        self.prefix_cache = prefix_cache
        # CoW pad width: a step writes at most chunk_size consecutive
        # positions per slot, spanning at most this many blocks — one
        # static width keeps one _copy_pages compilation.
        self._cow_pad = (-(-self.chunk_size // block_size) + 1) \
            if block_size else 0
        self._donate_cache = donate_cache
        self._ledger_kw = dict(decisions=offload_decisions,
                               host_sampling=host_sampling,
                               kv_quant=kv_quant, dp=self.dp, tp=self.tp)
        self._vlm = model.cfg.family == "vlm"
        # Telemetry: when enabled, serve() builds a StepTimeline per run
        # (strictly host-side — never touches a traced value, so the
        # step_compiles == 1 contract and token streams are unchanged).
        self.telemetry = telemetry
        self._timeline = None
        self._run_cow0 = 0
        self._fresh_arena_sched()
        self._step_compiles = 0

        # One backend decision for every Pallas kernel in the jitted
        # programs built here (the draft proposer reads the same one):
        # compiled on a TPU, interpreted only on the CPU.
        self.interpret = kernel_common.interpret_kernels()
        kw = dict(quant=quant, impl=impl, interpret=self.interpret)
        self._encode_cross = jax.jit(
            lambda p, f: model.encode_cross(p, f, **kw)) \
            if model.encode_cross is not None else None

        def model_pass(p, tokens, pos0, lengths, arena, rest):
            """The shared chunked model pass: resolve the trailing
            *rest* operands (paged block tables, vlm embed overrides)
            into decode_step kwargs — the one place the step entry
            contract lives, whichever sampling head sits on top."""
            kw2 = dict(kw)
            rest = list(rest)
            if self.paged:
                kw2["block_tables"] = rest.pop(0)
                kw2["paged_impl"] = paged_attn
            if self._vlm:
                kw2["embeds"] = rest.pop(0)
                kw2["embeds_mask"] = rest.pop(0)
            return model.decode_step(p, tokens, pos0, arena,
                                     lengths=lengths, **kw2)

        def pin_cache(arena):
            """Re-constrain the step's returned cache leaves to the
            arena's *committed* shardings. Without this, GSPMD may pick a
            different output layout than the input commitment, and the
            next call's donated-input sharding mismatch costs a re-jit —
            the step_compiles == 1 contract would silently break under a
            mesh."""
            if self.mesh is None or self.arena._shardings is None:
                return arena
            return jax.tree.map(jax.lax.with_sharding_constraint,
                                arena, self.arena._shardings)

        def step(p, tokens, pos0, lengths, active, arena, key, temps,
                 top_ks, top_ps, *rest):
            logits, arena = model_pass(p, tokens, pos0, lengths, arena,
                                       rest)
            arena = pin_cache(arena)
            with jax.named_scope("sample"):
                idx = jnp.maximum(lengths - 1, 0)
                last = jnp.take_along_axis(
                    logits, idx[:, None, None], axis=1)[:, 0]
                nxt = sampling.sample_slots(last, key, temps, active,
                                            top_k=top_ks, top_p=top_ps)
            return nxt, arena
        self._step = jax.jit(step,
                             donate_argnums=(5,) if donate_cache else ())

        def spec_step(p, tokens, pos0, lengths, prop_lens, active, arena,
                      key, temps, top_ks, top_ps, *rest):
            """The unified chunked step as a *verifier*: same model pass,
            but the sampling head sees every fed position's logits —
            ``verify_slots`` computes per-slot accept lengths and the one
            token emitted past the accepted prefix. Slots with zero
            proposals (prefill chunks, plain decode) degenerate to the
            ordinary ``lengths-1`` sampling row."""
            logits, arena = model_pass(p, tokens, pos0, lengths, arena,
                                       rest)
            arena = pin_cache(arena)
            with jax.named_scope("sample"):
                nxt, acc = sampling.verify_slots(
                    logits, tokens, key, temps, active,
                    prop_lens=prop_lens, lengths=lengths,
                    top_k=top_ks, top_p=top_ps)
            return nxt, acc, arena
        self._step_spec = jax.jit(
            spec_step, donate_argnums=(6,) if donate_cache else ()) \
            if spec != "off" else None

    # ------------------------------------------------------------------
    def lower_step(self):
        """Lower the serving step (the verify step when speculating) at
        this engine's shapes from abstract operands, without running it:
        ``lower_step().compile().as_text()`` shows what the backend
        compiler made of the step — e.g. whether the paged-attention
        kernel is a TPU custom call."""
        ns, C = self.num_slots, self.chunk_size
        i32, f32 = jnp.int32, jnp.float32

        def slot_spec(shape, dtype):
            sh = None if self.mesh is None else \
                shard_rules.slot_sharding(self.mesh, len(shape))
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

        def like(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        args = [jax.tree.map(like, self.params), slot_spec((ns, C), i32),
                slot_spec((ns,), i32), slot_spec((ns,), i32),
                slot_spec((ns,), jnp.bool_),
                jax.tree.map(like, self.arena.buffers),
                jax.ShapeDtypeStruct((2,), jnp.uint32),
                slot_spec((ns,), f32),
                slot_spec((ns,), i32), slot_spec((ns,), f32)]
        if self.spec != "off":
            args.insert(4, slot_spec((ns,), i32))
        if self.paged:
            args.append(jax.ShapeDtypeStruct(
                self.arena.tables.shape, i32,
                sharding=self.arena._table_sharding))
        if self._vlm:
            args += [slot_spec((ns, C, self.model.cfg.d_model), jnp.bfloat16),
                     slot_spec((ns, C), jnp.bool_)]
        fn = self._step_spec if self.spec != "off" else self._step
        with shard_rules.activation_mesh(self.mesh):
            return fn.lower(*args)

    def _fresh_arena_sched(self) -> None:
        if self.paged:
            self.arena = PagedKVArena(self.model, self.num_slots,
                                      self.max_seq,
                                      block_size=self._block_size,
                                      num_blocks=self._num_blocks,
                                      dtype=self.cache_dtype,
                                      prefix_cache=self.prefix_cache,
                                      kv_quant=self.kv_quant,
                                      mesh=self.mesh)
        else:
            self.arena = KVArena(self.model, self.num_slots, self.max_seq,
                                 dtype=self.cache_dtype, mesh=self.mesh)
        self.sched = Scheduler(self.num_slots, self.max_seq, dp=self.dp)
        # rid -> (hit_tokens, resident_growth_blocks) recorded by the
        # admission gate, consumed by _admit_chunked after seq.admit().
        self._pending_prefix: Dict[int, tuple] = {}

    def reset(self) -> None:
        """Fresh arena + scheduler, warm jit caches — serve() runs are
        independent, compilations are not repaid."""
        self._fresh_arena_sched()

    # ------------------------------------------------------------------
    def _try_admit(self, seq: Sequence) -> Optional[int]:
        """Arena-side admission gate. Contiguous arena: any free slot.
        Paged arena: a free slot AND the first chunk's block reservation,
        all-or-nothing (reservation then follows chunk progress)."""
        if not self.paged:
            return self.arena.alloc()
        if self.prefix_cache:
            got = self.arena.alloc_slot_prefix(seq.req.tokens,
                                               self.chunk_size)
            if got is None:
                return None
            slot, hit, growth = got
            self._pending_prefix[seq.rid] = (hit, growth)
            return slot
        first = min(seq.req.prompt_len, self.chunk_size)
        return self.arena.alloc_slot(self.arena.blocks_needed(first))

    def _admit_chunked(self, seq: Sequence, stats: GenStats,
                       ledger: TransferLedger) -> None:
        """Chunked admission: no prefill pass. Reset the slot's constant
        state leaves (stale recurrent/cross state from the previous
        occupant); enc-dec models additionally run the one-time encoder
        pass and scatter the cross KV into the slot. A prefix-cache hit
        recorded by the admission gate fast-forwards the sequence past
        the shared prompt positions — their KV already sits in mapped
        pages, so they are neither streamed nor recomputed, and only
        newly-resident blocks are charged as cache growth."""
        self.arena.reset_slot(seq.slot)
        if self._proposer is not None:
            reset = getattr(self._proposer, "reset_slot", None)
            if reset is not None:
                reset(seq.slot)             # draft arena slot turnover
            self._spec_ctrl.reset(seq.slot)
        hit, growth_blocks = self._pending_prefix.pop(seq.rid, (0, None))
        if self.paged:
            if growth_blocks is None:
                growth_blocks = len(self.arena.slot_blocks(seq.slot))
            ledger.charge_cache_growth(
                "prefill", growth_blocks * self.arena.block_bytes())
        if hit:
            seq.apply_prefix_hit(hit)
            stats.prefix_hits += 1
            stats.prefix_hit_tokens += hit
            ledger.record_prefix_hit(hit)
        if self._encode_cross is not None and seq.req.extras \
                and "frames" in seq.req.extras:
            t0 = time.perf_counter()
            frames = jnp.asarray(seq.req.extras["frames"])
            cache = self._encode_cross(self.params, frames)
            self.arena.write_prefill(cache, seq.slot)
            jax.block_until_ready(jax.tree.leaves(self.arena.buffers)[0])
            stats.prefill_s += time.perf_counter() - t0
            ledger.charge("prefill", "acts", "h2d", frames.nbytes)
            cross_bytes = sum(
                x.size * x.dtype.itemsize
                for x in jax.tree.leaves(cache["dec_layers"]["cross"]))
            ledger.charge_cache_growth("prefill", cross_bytes)

    def _preempt(self, seq: Sequence) -> None:
        """Recompute-preemption: reclaim the victim's slot and blocks
        (a decref per block — pages shared with siblings stay resident)
        and push it back to the queue head. Speculative per-slot state
        (accept-rate EMA, draft-arena mirror) is reset here, not only at
        slot reuse: a preempted-then-readmitted sequence must restart
        from clean speculation state, whichever slot it lands in."""
        slot = self.sched.preempt(seq)
        if self._proposer is not None:
            reset = getattr(self._proposer, "reset_slot", None)
            if reset is not None:
                reset(slot)
            self._spec_ctrl.reset(slot)
        self.arena.free_slot(slot)

    def _reserve_blocks(self, ledger: TransferLedger) -> None:
        """Grow each active sequence's block table to cover its next feed
        (``seq.position + next_feed`` covered positions — one token for a
        decoding slot, up to a whole chunk for a prefilling one).
        Oldest-first, so under scarcity the last free block goes to the
        sequence preemption would keep (granting it youngest-first would
        hand a block to the imminent victim and waste it). On allocator
        exhaustion, preempt the youngest active sequence and retry; age
        order guarantees the oldest sequence can always run alone, so the
        stream never deadlocks."""
        by_age = sorted(self.sched.active.values(),
                        key=lambda s: s.admit_seq)
        for seq in by_age:
            slot = seq.slot
            if self.sched.active.get(slot) is not seq:
                continue                        # preempted by an earlier turn
            phase = "prefill" if seq.state is SeqState.PREFILL else "decode"
            while True:
                bound = self._next_feed_bound(seq)
                fresh = self.arena.ensure(slot, seq.position + bound)
                if fresh is None:
                    victim = self.sched.preempt_victim()
                    self._preempt(victim)
                    if victim is seq:
                        break                   # evicted ourselves: skip step
                    continue
                if fresh:
                    ledger.charge_cache_growth(
                        phase, fresh * self.arena.block_bytes())
                if not self.prefix_cache:
                    break
                # Copy-on-write barrier: any shared block the coming
                # write range maps to is split now, so the collision-free
                # scatter invariant holds before the step launches. A
                # fresh copy is arena growth like any other block.
                cow = self.arena.prepare_write(slot, seq.position, bound,
                                               self._cow_pad)
                if cow is None:
                    victim = self.sched.preempt_victim()
                    self._preempt(victim)
                    if victim is seq:
                        break                   # evicted ourselves: skip step
                    continue
                if cow:
                    ledger.charge_cache_growth(
                        phase, cow * self.arena.block_bytes())
                break

    def _next_feed_bound(self, seq: Sequence) -> int:
        """Upper bound on the tokens ``seq`` feeds next step — what block
        reservation must cover. A speculating decode slot may feed its
        committed token plus up to its current proposal depth; proposal
        lanes that end up trimmed or unfilled leave blocks reserved one
        step early (reclaimed by the rollback tail trim or sequence
        growth, never leaked)."""
        base = seq.next_feed(self.chunk_size)
        if self.spec != "off" and seq.state is SeqState.DECODE:
            return base + self._spec_depth(seq)
        return base

    def _spec_depth(self, seq: Sequence) -> int:
        """Proposal lanes this sequence wants: the controller's adaptive
        depth, capped so speculation never proposes past the sequence's
        own generation budget (the final token is always sampled by a
        plain lane — proposals beyond it could never be accepted)."""
        rem = seq.req.max_new_tokens - seq.tokens_out
        return max(0, min(self._spec_ctrl.depth(seq.slot),
                          rem - 1, self.chunk_size - 1))

    # ------------------------------------------------------------------
    def _sampling_vectors(self, seqs: Dict[int, Sequence]):
        """Per-slot temperature/top_k/top_p arrays (engine defaults fill
        request-level unset values)."""
        ns = self.num_slots
        temps = np.zeros((ns,), np.float32)
        top_ks = np.zeros((ns,), np.int32)
        top_ps = np.ones((ns,), np.float32)
        for slot, seq in seqs.items():
            sp = seq.req.sampling
            temps[slot] = sp.temperature
            top_ks[slot] = sp.top_k if sp.top_k else self.top_k
            top_ps[slot] = sp.top_p if sp.top_p < 1.0 else self.top_p
        return temps, top_ks, top_ps

    def _vision_override(self, feeds: Dict[int, int]):
        """(embeds, mask, bytes) chunk-slice of each prefilling vlm slot's
        stub patch embeddings: positions [fed, fed+n) below vision_tokens
        take the provided embedding instead of the token embedding."""
        ns, C = self.num_slots, self.chunk_size
        d = self.model.cfg.d_model
        embeds = np.zeros((ns, C, d), np.float32)
        mask = np.zeros((ns, C), bool)
        nbytes = 0
        for slot, seq in self.sched.active.items():
            if seq.state is not SeqState.PREFILL or not seq.req.extras:
                continue
            vis = seq.req.extras.get("vision_embeds")
            if vis is None:
                continue
            vis = np.asarray(vis)[0]                      # (V, d)
            n = feeds.get(slot, 0)
            lo, hi = seq.fed, min(seq.fed + n, vis.shape[0])
            if hi > lo:
                embeds[slot, :hi - lo] = vis[lo:hi]
                mask[slot, :hi - lo] = True
                nbytes += (hi - lo) * d * 2               # bf16 upload
        return embeds, mask, nbytes

    def _plan_feeds(self):
        """This step's feed per active slot (``Scheduler.plan_feeds``)
        and, when speculating, the proposals for the budget-granted
        lanes: ``(feeds, proposals)``."""
        C = self.chunk_size
        proposals: Dict[int, np.ndarray] = {}
        if self.spec == "off":
            return self.sched.plan_feeds(C, self.step_token_budget), \
                proposals
        desires = {slot: d for slot, seq in self.sched.active.items()
                   if seq.state is SeqState.DECODE
                   and (d := self._spec_depth(seq)) > 0}
        feeds = self.sched.plan_feeds(C, self.step_token_budget, desires)
        # Propose only the budget-granted lanes (the draft proposer pays
        # real steps per lane; the n-gram proposer may return fewer than
        # granted — or nothing — when no suffix matches).
        grants = {s: feeds[s] - 1 for s in desires if feeds[s] > 1}
        if grants:
            proposals = self._proposer.propose(self.sched.active, grants)
            for slot, g in grants.items():
                got = proposals.get(slot)
                unfilled = g - (0 if got is None else int(got.size))
                if unfilled > 0:
                    # Lanes the proposer could not fill are zero-value
                    # evidence: decay the depth EMA so a slot with no
                    # matchable suffix stops reserving speculative paged
                    # blocks it never uses (depth floors at 1).
                    self._spec_ctrl.update(slot, unfilled, 0)
        return feeds, proposals

    def _step_once(self, key, feeds: Dict[int, int],
                   proposals: Dict[int, np.ndarray], stats: GenStats,
                   ledger: TransferLedger, t0: float) -> None:
        """One unified (slots, chunk) step over the planned ``feeds``:
        prompt chunks and decode feedback tokens ride the same traced
        shape. Its host phases are spans (``serve.operands``,
        ``serve.dispatch``, ``serve.sync``, ``serve.bookkeep``); the
        step's ``t_start``/``t_end`` are the dispatch's start and the
        sync's end. Token timestamps are read *after* the step's host
        sync so TTFT/latency include the step (and any first-step
        compile) that produced each token."""
        ns, C = self.num_slots, self.chunk_size
        spec_on = self.spec != "off"
        tl = self._timeline
        with span("serve.operands", tl):
            tokens = np.zeros((ns, C), np.int32)
            pos0 = np.zeros((ns,), np.int32)
            lens = np.zeros((ns,), np.int32)
            prop_lens = np.zeros((ns,), np.int32)
            active = np.zeros((ns,), bool)
            for slot, seq in self.sched.active.items():
                n = feeds[slot]
                if seq.state is SeqState.PREFILL:
                    tokens[slot, :n] = seq.req.tokens[seq.fed:seq.fed + n]
                else:
                    tokens[slot, 0] = seq.next_token
                    props = proposals.get(slot)
                    if props is not None and props.size:
                        kp = min(int(props.size), n - 1)
                        tokens[slot, 1:1 + kp] = props[:kp]
                        prop_lens[slot] = kp
                    n = 1 + int(prop_lens[slot])  # actual feed may undershoot
                    feeds[slot] = n               # the plan (lanes unfilled)
                pos0[slot] = seq.position
                lens[slot] = n
                active[slot] = True
            temps, top_ks, top_ps = self._sampling_vectors(self.sched.active)

        with span("serve.dispatch", tl) as dispatch:
            before = self._jit_cache_size()
            if self.mesh is None:
                put = jnp.asarray
            else:
                # Commit per-slot operands with the slot axis over 'data' so
                # GSPMD partitions the step along slots without a gather.
                def put(a):
                    a = np.asarray(a)
                    return jax.device_put(
                        a, shard_rules.slot_sharding(self.mesh, a.ndim))
            step_args = [self.params, put(tokens), put(pos0),
                         put(lens), put(active),
                         self.arena.buffers, key, put(temps),
                         put(top_ks), put(top_ps)]
            if spec_on:
                step_args.insert(4, put(prop_lens))
            if self.paged:
                dev_tables, uploaded = self.arena.device_tables()
                step_args.append(dev_tables)
                if uploaded:    # dirty tables only: admission/growth/preempt
                    ledger.charge("decode", "tables", "h2d", uploaded)
            if self._vlm:
                embeds, emask, vis_bytes = self._vision_override(feeds)
                step_args += [jnp.asarray(embeds, jnp.bfloat16),
                              jnp.asarray(emask)]
                if vis_bytes:
                    ledger.charge("prefill", "acts", "h2d", vis_bytes)
            # The scope makes the MoE token-path replication pin live during
            # the step *trace* (first call only; later calls hit the jit
            # cache). Committed input shardings carry everything else — see
            # parallel/sharding.py for why no other in-graph constraint may
            # appear (each one perturbs fusion and hence bf16 rounding).
            with shard_rules.activation_mesh(self.mesh):
                if spec_on:
                    # The verify step IS the chunked step with the
                    # verification sampling head; spec engines run it
                    # exclusively (zero proposals degenerate to plain
                    # sampling), so the jit cache still holds exactly one
                    # step compilation.
                    nxt, acc, self.arena.buffers = self._step_spec(*step_args)
                else:
                    nxt, self.arena.buffers = self._step(*step_args)
                    acc = None
        with span("serve.sync", tl) as sync:
            acc_host = np.asarray(acc) if acc is not None else None
            nxt_host = np.asarray(nxt)        # blocks until step completes
        t_step, t_end = dispatch.t0, sync.t1
        now = t_end - t0
        with span("serve.bookkeep", tl):
            self._bookkeep(feeds, tokens, pos0, lens, prop_lens, nxt_host,
                           acc_host, before, t_step - t0, now, stats,
                           ledger)

    def _bookkeep(self, feeds, tokens, pos0, lens, prop_lens, nxt_host,
                  acc_host, before: int, t_start: float, now: float,
                  stats: GenStats, ledger: TransferLedger) -> None:
        """Everything a step leaves to the host after its sync: compile
        count, time split, ledger charges, resident bytes, the paged-read
        count, token records, the step event and slot retirement."""
        ns, C = self.num_slots, self.chunk_size
        dcomp = self._jit_cache_size() - before
        self._step_compiles += dcomp
        pre_toks = sum(n for s, n in feeds.items()
                       if self.sched.active[s].state is SeqState.PREFILL)
        dec_toks = sum(n for s, n in feeds.items()
                       if self.sched.active[s].state is SeqState.DECODE)
        frac = pre_toks / max(pre_toks + dec_toks, 1)
        dt = now - t_start
        stats.prefill_s += dt * frac
        stats.decode_s += dt * (1.0 - frac)
        ledger.charge_step_weights(prefill_frac=frac)

        resident = self.arena.resident_bytes()
        stats.peak_resident_bytes = max(stats.peak_resident_bytes, resident)
        stats.resident_bytes_sum += resident
        stats.live_tokens_sum += int(sum(
            s.position + feeds[slot]
            for slot, s in self.sched.active.items()))
        if self.paged and self.arena.has_paged:
            bsz, mb = self.arena.block_size, self.arena.max_blocks
            rep_sz = ns // self.dp      # slots per 'data' replica
            per_rep = np.zeros((self.dp,))
            if self.paged_attn == "fused":
                # The kernel's exact fetch count: a slot row walks blocks
                # 0..(pos0 + max(lengths,1) - 1)//bs (its last *valid*
                # query's causal depth; dead trailing grid steps clamp to
                # that block), and Pallas elides the fetch whenever the
                # resolved page repeats — so count distinct consecutive
                # pages in each row's clamped walk (an idle slot's
                # all-null row costs exactly one null-page fetch). Under
                # DP each replica walks only its own slots' rows, so the
                # per-device figure is the busiest replica's share.
                tb = self.arena.tables
                for s in range(ns):
                    depth = int(pos0[s]) + max(int(lens[s]), 1) - 1
                    walk = tb[s, :min(depth // bsz, mb - 1) + 1]
                    per_rep[s // rep_sz] += \
                        1 + int(np.sum(walk[1:] != walk[:-1]))
            else:
                # Dense gather of every table row; each replica only
                # materializes the view for its local slot rows.
                per_rep[:] = rep_sz * mb
            bb = self.arena.block_bytes()
            stats.paged_kv_read_bytes += float(per_rep.sum()) * bb
            stats.paged_kv_read_bytes_per_device += float(per_rep.max()) * bb
        tok_bytes = 0.0 if self.paged else self.arena.token_bytes()
        tl = self._timeline
        slot_mix = [] if tl is not None else None
        for slot, seq in list(self.sched.active.items()):
            n = feeds[slot]
            if seq.state is SeqState.PREFILL:
                if n == 0:
                    if slot_mix is not None:      # deferred: fed nothing
                        slot_mix.append((slot, seq.rid, "prefill", 0, 0))
                    continue                  # budget-starved this step
                stats.prefill_tokens += n
                ledger.charge_chunk("prefill", n, seq.fed + n)
                if tok_bytes:
                    ledger.charge_cache_growth("prefill", n * tok_bytes)
                first_tok = 0
                if seq.feed_chunk(n):
                    seq.start_decode()        # this chunk sampled token 0
                    if self.prefix_cache:
                        # Prefill complete: positions [0, prompt_len) are
                        # all written, decode writes land strictly past
                        # them — publish the full prompt blocks.
                        self.arena.register_prefix(seq.slot,
                                                   seq.req.tokens)
                    ledger.charge_sampled()
                    seq.record_token(int(nxt_host[slot]), now)
                    stats.decode_tokens += 1
                    first_tok = 1
                    if tl is not None:
                        tl.on_token(seq.rid, now, ttft_s=seq.ttft_s)
                        if seq.done:
                            tl.on_done(seq.rid, seq.latency_s)
                if slot_mix is not None:
                    slot_mix.append((slot, seq.rid, "prefill", n,
                                     first_tok))
            else:
                m = n                         # 1 committed + kp proposals
                kp = int(prop_lens[slot])
                ledger.charge_chunk("decode", m, int(pos0[slot]) + m)
                if kp == 0:
                    emitted = [int(nxt_host[slot])]
                else:
                    a = min(int(acc_host[slot]), kp)
                    emitted = [int(t) for t in tokens[slot, 1:1 + a]]
                    emitted.append(int(nxt_host[slot]))
                    stats.spec_proposed += kp
                    stats.spec_accepted += a
                    self._spec_ctrl.update(slot, kp, a)
                r = 0
                for t in emitted:
                    if seq.done:
                        break                 # generation budget exhausted
                    first = seq.t_first_token is None
                    seq.record_token(t, now)
                    r += 1
                    if tl is not None:
                        tl.on_token(seq.rid, now,
                                    ttft_s=seq.ttft_s if first else None)
                if tl is not None and seq.done:
                    tl.on_done(seq.rid, seq.latency_s)
                if tok_bytes:
                    ledger.charge_cache_growth("decode", r * tok_bytes)
                # Host sampling would drain every fed lane's logit row
                # (rejected lanes included) — charge the full feed width.
                ledger.charge_sampled(r, logit_rows=m)
                stats.decode_tokens += r
                if m > r:
                    # Rejected-suffix rollback: zero KV positions
                    # [pos0 + r, pos0 + m) and (paged) trim the block
                    # table past the surviving prefix.
                    self.arena.rollback(slot, int(pos0[slot]) + r, m - r,
                                        C)
                    stats.spec_rolled_back += m - r
                if slot_mix is not None:
                    slot_mix.append((slot, seq.rid,
                                     "verify" if kp else "decode", m, r))
        stats.steps += 1
        self.sched.record_step()
        if tl is not None:
            # Cumulative run-relative counters; the timeline diffs them
            # into per-step deltas (sums telescope back to run totals).
            counters = {
                "steps": float(stats.steps),
                "prefill_tokens": float(stats.prefill_tokens),
                "decode_tokens": float(stats.decode_tokens),
                "spec_proposed": float(stats.spec.proposed),
                "spec_accepted": float(stats.spec.accepted),
                "spec_rolled_back": float(stats.spec.rolled_back),
                "prefix_hits": float(stats.prefix.hits),
                "prefix_hit_tokens": float(stats.prefix.hit_tokens),
                "cow_splits": float(self.arena.cow_splits
                                    - self._run_cow0)
                if self.paged else 0.0,
                "preemptions": float(self.sched.stats.preemptions),
                "deferred_feeds": float(self.sched.stats.deferred_feeds),
                "prefill_chunks": float(self.sched.stats.prefill_chunks),
                "paged_kv_read_bytes": stats.paged.read_bytes,
            }
            if self._proposer is not None:
                counters["draft_steps"] = float(
                    getattr(self._proposer, "steps", 0))
            tl.record_step(
                t_start=t_start, t_end=now,
                occupancy=len(self.sched.active), compiles=dcomp,
                counters=counters,
                gauges={"resident_bytes": float(resident),
                        "queue_len": float(len(self.sched.queue))},
                slots=slot_mix)
        self.sched.retire(self.arena.free)

    def _jit_cache_size(self) -> int:
        total = 0
        for fn in (self._step, self._step_spec if self.spec != "off"
                   else None):
            size = getattr(fn, "_cache_size", None)
            total += size() if callable(size) else 0
        return total

    # ------------------------------------------------------------------
    def serve(self, requests: List[Request], *, seed: int = 0,
              realtime: bool = True) -> ServeReport:
        """Run a request stream to completion. ``realtime``: honor
        ``arrival_s`` offsets against the wall clock (sleep while idle);
        False replays arrivals against the virtual step clock only.

        Each serve() run is an independent request stream: the scheduler
        (queue, registry, stats) is rebuilt per run. The *arena* is not —
        a later run on the same engine decodes against warm storage, so
        with ``prefix_cache`` enabled, pages published by one run are hit
        by the next (the system-prompt-across-streams case). ``reset()``
        additionally rebuilds the arena, dropping the cache."""
        if self.sched.stats.steps or self.sched.finished:
            self.sched = Scheduler(self.num_slots, self.max_seq, dp=self.dp)
            self._pending_prefix.clear()
        if self.paged:
            for r in requests:
                # Last cache write lands at position prompt+gen-2 (the
                # final sampled token is returned, never inserted), so
                # peak demand is prompt+gen-1 covered positions.
                need = self.arena.blocks_needed(r.prompt_len
                                                + r.max_new_tokens - 1)
                if need > self.arena.num_blocks:
                    raise ValueError(
                        f"request {r.rid}: needs {need} blocks at full "
                        f"length, arena has {self.arena.num_blocks} — "
                        f"could never finish even running alone")
        for r in requests:
            self.sched.submit(r)
        if self._proposer is not None:
            reset_run = getattr(self._proposer, "reset_run", None)
            if reset_run is not None:
                reset_run()         # fresh draft ledger per serve run
        stats = GenStats()
        ledger = TransferLedger(self.model.cfg, self.quant,
                                **self._ledger_kw)
        # The arena (and its prefix cache) outlives serve() runs — a warm
        # cache is the point — so per-run CoW counts are deltas.
        cow0 = self.arena.cow_splits if self.paged else 0
        self._run_cow0 = cow0
        tl = None
        if self.telemetry:
            # Created AFTER the proposer's reset_run so the draft tap
            # lands on this run's fresh draft ledger; detached again in
            # finalize() before the report is assembled.
            tl = StepTimeline(
                ledger,
                draft_ledger=getattr(self._proposer, "ledger", None),
                meta={"arch": self.model.cfg.name, "quant": self.quant,
                      "slots": self.num_slots, "chunk": self.chunk_size,
                      "dp": self.dp, "tp": self.tp, "spec": self.spec,
                      "kv_quant": self.kv_quant, "paged": self.paged})
            self._timeline = tl
            self.sched.telemetry = tl
        key = jax.random.PRNGKey(seed)
        t0 = time.perf_counter()
        if tl is not None:
            tl.origin = t0

        # Each iteration is one profiler step (numbered by the step it
        # runs, or would run next) tiled by disjoint host-phase spans:
        # serve.schedule here, then serve.operands, serve.dispatch,
        # serve.sync and serve.bookkeep in _step_once — or serve.wait
        # when no slot is active.
        while self.sched.has_work:
            with jax.profiler.StepTraceAnnotation("serve_step",
                                                  step_num=stats.steps):
                if tl is not None:
                    tl.open_iteration()
                with span("serve.schedule", tl):
                    now = time.perf_counter() - t0
                    if self.paged:
                        # Incumbents reserve their next-feed blocks BEFORE
                        # new admissions take them (may preempt-to-queue):
                        # admitting first could burn ingestion work on a
                        # sequence that the very next reserve pass would
                        # evict. A fresh admission's first feed is covered
                        # by its own admission reservation, so skipping it
                        # here is safe.
                        self._reserve_blocks(ledger)
                    admitted = self.sched.admit(self._try_admit, now)
                    for seq in admitted:
                        self._admit_chunked(seq, stats, ledger)
                    if self.sched.active:
                        key, sub = jax.random.split(key)
                        feeds, proposals = self._plan_feeds()
                if self.sched.active:
                    self._step_once(sub, feeds, proposals, stats, ledger,
                                    t0)
                    continue
                if self.sched.queue:
                    continue    # preempted/starved: blocks freed, re-admit
                nxt = self.sched.next_arrival()
                if nxt is None:
                    break               # queued-but-no-slot cannot happen here
                with span("serve.wait", tl):
                    if realtime:
                        time.sleep(min(max(nxt - now, 0.0), 0.05))
                    else:
                        self.sched.poll_arrivals(float("inf"))

        if tl is not None:
            tl.finalize(time.perf_counter() - t0)
            self._timeline = None
            self.sched.telemetry = None
        stats.cache_bytes = self.arena.nbytes()
        if self.paged:
            stats.cow_splits = self.arena.cow_splits - cow0
        stats.tokens_in = sum(r.prompt_len for r in requests)
        stats.tokens_out = sum(s.tokens_out for s in self.sched.finished)
        stats.transfers = TransferReport.from_ledger(ledger)
        draft_ledger = getattr(self._proposer, "ledger", None)
        if draft_ledger is not None:
            stats.draft_transfers = TransferReport.from_ledger(draft_ledger)
        order = {r.rid: i for i, r in enumerate(requests)}
        seqs = sorted(self.sched.finished, key=lambda s: order[s.rid])
        return ServeReport(stats=stats, sequences=seqs,
                           sched=self.sched.stats,
                           step_compiles=self._step_compiles,
                           ledger=ledger, timeline=tl)


class Engine:
    """Thin fixed-batch compatibility wrapper over ``ServingEngine``.

    ``generate(tokens, n)`` submits one request per batch row (identical
    lengths, simultaneous arrival) and reassembles a dense (B, n) output —
    the legacy lockstep interface, now running on the slot arena. Since
    top_k/top_p became per-slot *data* in the jitted step, the engine
    cache is keyed by batch size alone — mixed sampling configs no longer
    fragment it."""

    def __init__(self, model: ModelAPI, params, *, quant: str = "none",
                 max_seq: int = 2048, impl: str = "ref",
                 chunk_size: int = 8, donate_cache: bool = True):
        self.model = model
        self.params = params
        self.quant = quant
        self.max_seq = max_seq
        self.impl = impl
        self.chunk_size = chunk_size
        self.donate_cache = donate_cache
        self._engines: Dict[int, ServingEngine] = {}    # batch -> engine

    @classmethod
    def from_dense(cls, model: ModelAPI, dense_params, quant: str,
                   **kw) -> "Engine":
        """llama.cpp-style model quantization stage + engine construction."""
        qparams = convert.quantize_params(dense_params, quant) \
            if quant != "none" else dense_params
        return cls(model, qparams, quant=quant, **kw)

    def _engine_for(self, batch: int) -> ServingEngine:
        if batch not in self._engines:
            self._engines[batch] = ServingEngine(
                self.model, self.params, quant=self.quant,
                num_slots=batch, max_seq=self.max_seq, impl=self.impl,
                chunk_size=self.chunk_size,
                donate_cache=self.donate_cache)
        else:
            # fresh arena/scheduler, warm jit caches
            self._engines[batch].reset()
        return self._engines[batch]

    @staticmethod
    def _release(eng: ServingEngine) -> None:
        """Drop the arena's device buffers and the run's sequence registry
        between generate() calls — only the warm jit caches are worth
        keeping alive (a full-size arena is GBs of device memory)."""
        eng.arena = None
        eng.sched = None

    def generate(self, tokens: jnp.ndarray, max_new_tokens: int, *,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: int = 0,
                 extras: Optional[Dict] = None):
        """tokens: (B, S_prompt) int32. Returns (out_tokens (B, T), stats)."""
        b, s_prompt = tokens.shape
        assert s_prompt + max_new_tokens <= self.max_seq, "KV arena too small"
        eng = self._engine_for(b)
        samp = SamplingParams(temperature=temperature, top_k=top_k,
                              top_p=top_p, seed=seed)
        toks_np = np.asarray(tokens)
        reqs = []
        for i in range(b):
            ex = {k: v[i:i + 1] for k, v in extras.items()} if extras else None
            reqs.append(Request(rid=i, tokens=toks_np[i],
                                max_new_tokens=max_new_tokens,
                                sampling=samp, extras=ex))
        try:
            report = eng.serve(reqs, seed=seed, realtime=False)
        finally:
            self._release(eng)
        out = jnp.asarray(
            np.stack([np.asarray(s.generated, np.int32)
                      for s in report.sequences]))
        stats = report.stats
        # Legacy per-sequence semantics for the fixed-batch interface.
        stats.tokens_in = s_prompt
        stats.tokens_out = max_new_tokens
        return out, stats
