"""Serving launcher — continuous-batching request-stream driver.

Stream mode (default): replay a stream of staggered requests (Poisson or
back-to-back arrivals) through the slot-arena engine, reporting per-request
latency percentiles, throughput vs batch occupancy, and the transfer
ledger's bytes-per-token breakdown (the paper's §V.A bottleneck metric,
measured live instead of modeled):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --quant q8_0 --requests 8 --slots 4 --arrival poisson --rate 4

Prompts stream through the unified chunked-prefill step
(``--chunk-size`` tokens per slot per iteration, one traced shape, no
pow2 padding — the legacy bucketed prefill pass is retired):

  PYTHONPATH=src python -m repro.launch.serve --reduced --requests 8 \
      --chunk-size 4              # unified step, 4-token prompt chunks

Paged KV arena (block-table allocation: admit on free blocks, grow
tables with chunk progress, preempt-to-queue on exhaustion). Paged K/V
is attended by the fused block-table Pallas kernel by default;
``--paged-attn ref`` keeps the dense-gather oracle:

  PYTHONPATH=src python -m repro.launch.serve --reduced --requests 12 \
      --slots 8 --block-size 8 --num-blocks 16 --paged-attn fused

Quantized KV pages (``--kv-quant int8``): the paged arena stores int8
codes plus per-(position, kv-head) fp16 scales and the fused kernel
dequantizes in-block during the table walk, roughly halving both the
per-token KV stream and arena residency (see docs/kernel-contracts.md):

  PYTHONPATH=src python -m repro.launch.serve --reduced --requests 12 \
      --slots 8 --block-size 8 --kv-quant int8

Speculative decoding (propose k tokens, verify them in ONE unified step,
amortize the per-step weight stream by the accept length — §V.A's
transfer bottleneck attacked at the system level). ``--spec ngram`` is
the model-free prompt-lookup drafter; ``--spec draft`` runs a small
draft model (own arena, own ledger account):

  PYTHONPATH=src python -m repro.launch.serve --reduced --requests 8 \
      --spec ngram --spec-k 4 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
      --requests 8 --spec draft --spec-draft-model qwen3-0.6b

Sharded serving (``--dp``/``--tp``): the same jitted step runs over a
``(data, model)`` device mesh — slots data-parallel over 'data', heads
and weight-stream tensors tensor-parallel over 'model' — with
token-identical outputs and per-device ledger accounting. Testable on
CPU by forcing host devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.serve --reduced --requests 8 \
      --slots 4 --dp 2 --tp 2 --block-size 8

Batch mode (legacy lockstep interface, kept for the paper's fixed [in:out]
workload grid):

  PYTHONPATH=src python -m repro.launch.serve --reduced --mode batch \
      --prompt-len 32 --gen 16 --batch 4
"""
from __future__ import annotations

import argparse
import dataclasses
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.launch.mesh import make_mesh
from repro.models.api import build_model
from repro.runtime.engine import Engine, ServingEngine
from repro.runtime.request import Request, SamplingParams
from repro.runtime.telemetry import serve_report_lines


#: The one persistent compilation cache directory used when
#: ``JAX_COMPILATION_CACHE_DIR`` is not set: fixed, inside the checkout
#: and ignored by git (the path is part of the cache key, so it never
#: moves between runs).
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing is set here; otherwise the cache goes to
    ``COMPILE_CACHE_DIR``. Entry points call this, never an import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def make_extras(cfg, batch: int):
    extras = {}
    if cfg.family == "vlm":
        extras["vision_embeds"] = jnp.zeros(
            (batch, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "encdec":
        extras["frames"] = jnp.zeros(
            (batch, cfg.encoder_seq_len, cfg.d_model), jnp.bfloat16)
    return extras


def build_stream(cfg, args, rng: np.random.RandomState):
    """Request stream with randomized lengths + Poisson/back-to-back
    arrival offsets."""
    lo = max(args.prompt_len // 2, 2)
    samp = SamplingParams(temperature=args.temperature)
    shared = rng.randint(0, cfg.vocab_size, args.shared_prefix) \
        if args.shared_prefix else None
    t = 0.0
    reqs = []
    for i in range(args.requests):
        L = int(rng.randint(lo, args.prompt_len + 1))
        if args.arrival == "poisson" and args.rate > 0:
            t += float(rng.exponential(1.0 / args.rate))
        extras = make_extras(cfg, 1)
        tokens = rng.randint(0, cfg.vocab_size, L)
        if shared is not None:
            # system-prompt workload: every request opens with the same
            # token prefix (what --prefix-cache deduplicates)
            tokens = np.concatenate([shared, tokens])
        reqs.append(Request(
            rid=i, tokens=tokens,
            max_new_tokens=args.gen, sampling=samp,
            arrival_s=t if args.arrival == "poisson" else 0.0,
            extras=extras or None))
    return reqs


def offload_decisions(cfg, quant: str, seq: int, n_out: int):
    """Static offload table (paper Table 2) applied to the live ledger so
    host-resident kernels charge no DMA bytes."""
    from repro.core.imax_model import asic_28nm
    from repro.core.offload import OffloadPolicy, model_kernel_calls

    q = quant if quant != "none" else "fp16"
    prefill = model_kernel_calls(cfg, q, seq, 1, decode=False)
    decode = [dataclasses.replace(c, count=c.count * n_out)
              for c in model_kernel_calls(cfg, q, seq, 1, decode=True)]
    by_name = {}
    for c in prefill + decode:
        by_name.setdefault(c.name, []).append(c)
    return OffloadPolicy(asic_28nm()).decide_table(prefill, by_name)


def build_draft(args):
    """Draft model + params for ``--spec draft`` (reduced tracks the
    target's --reduced; params are quantized with the serve quant so the
    draft's ledger account charges the same recipe)."""
    dcfg = get_config(args.spec_draft_model)
    if args.reduced:
        dcfg = dcfg.reduced()
    dmodel = build_model(dcfg)
    dparams = dmodel.init(jax.random.PRNGKey(args.seed + 17))
    if args.quant != "none":
        from repro.core import convert
        dparams = convert.quantize_params(dparams, args.quant)
    return dmodel, dparams


def run_stream(cfg, model, params, args) -> None:
    rng = np.random.RandomState(args.seed)
    reqs = build_stream(cfg, args, rng)
    max_seq = args.max_seq or (args.shared_prefix + args.prompt_len
                               + args.gen)
    decisions = offload_decisions(cfg, args.quant, args.prompt_len,
                                  args.gen) if args.offload_policy else None
    if args.quant != "none":
        from repro.core import convert
        params = convert.quantize_params(params, args.quant)
    draft_model = draft_params = None
    if args.spec == "draft":
        draft_model, draft_params = build_draft(args)
    engine = ServingEngine(
        model, params, quant=args.quant, num_slots=args.slots,
        max_seq=max_seq, offload_decisions=decisions,
        chunk_size=args.chunk_size,
        block_size=args.block_size or None, num_blocks=args.num_blocks
        or None, paged_attn=args.paged_attn or "fused",
        spec=args.spec, spec_k=args.spec_k or 4,
        spec_draft_model=draft_model, spec_draft_params=draft_params,
        prefix_cache=args.prefix_cache, kv_quant=args.kv_quant,
        host_sampling=args.host_sampling, mesh=build_mesh(args),
        telemetry=True)

    report = engine.serve(reqs, seed=args.seed)
    arena_desc = f"slots={args.slots}"
    if engine.paged:
        arena_desc += (f" paged[{engine.arena.num_blocks}x"
                       f"{engine.arena.block_size} "
                       f"attn={engine.paged_attn}"
                       + (f" kv={engine.kv_quant}"
                          if engine.kv_quant != "none" else "") + "]")
    print(f"arch={cfg.name} quant={args.quant} stream={args.requests} reqs "
          f"({args.arrival}) {arena_desc} "
          f"prefill=chunked[{engine.chunk_size}] gen={args.gen}")
    # ONE formatter emits every report line (scheduler, arena, spec,
    # prefix, timing, percentiles, mesh, ledger, host phases) — the
    # hand-rolled print block and TransferReport.summary_lines used to
    # drift apart; see telemetry.serve_report_lines.
    for line in serve_report_lines(engine, report,
                                   total_requests=args.requests):
        print(f"  {line}")
    first = report.sequences[0]
    print(f"  first request tokens: {first.generated[:8]}")
    if args.metrics_out:
        report.timeline.write_metrics_jsonl(args.metrics_out)
        print(f"  metrics: wrote {len(report.timeline.events)} step "
              f"events to {args.metrics_out}")
    if args.trace:
        report.timeline.write_chrome_trace(args.trace)
        print(f"  trace: wrote Perfetto/Chrome trace to {args.trace} "
              f"(open at https://ui.perfetto.dev)")


def run_batch(cfg, model, params, args) -> None:
    max_seq = args.max_seq or (args.prompt_len + args.gen)
    engine = Engine.from_dense(model, params, args.quant, max_seq=max_seq)
    key = jax.random.PRNGKey(args.seed + 1)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size, jnp.int32)
    extras = make_extras(cfg, args.batch)
    out, stats = engine.generate(prompt, args.gen,
                                 temperature=args.temperature,
                                 extras=extras or None)
    print(f"arch={cfg.name} quant={args.quant} "
          f"[{args.prompt_len}:{args.gen}] batch={args.batch}")
    # decode_tok_per_s aggregates the whole batch; divide for per-sequence.
    print(f"  prefill {stats.prefill_s*1e3:.1f} ms | "
          f"decode {stats.decode_s*1e3:.1f} ms "
          f"({stats.decode_tok_per_s/args.batch:.1f} tok/s/seq, "
          f"{stats.decode_tok_per_s:.1f} tok/s total) | "
          f"cache {stats.cache_bytes/1e6:.1f} MB | "
          f"bytes/token {stats.transfers.bytes_per_token/1e6:.2f} MB")
    print(f"  first generated tokens: {out[0, :8].tolist()}")


def validate_args(ap, args) -> None:
    """Fail fast on incompatible flag combinations instead of silently
    falling back — a typo'd serve invocation should die with a usable
    message, not measure the wrong configuration."""
    if args.num_blocks and not args.block_size:
        ap.error("--num-blocks requires --block-size (paged arena)")
    if args.prefix_cache:
        if not args.block_size:
            ap.error("--prefix-cache requires the paged arena "
                     "(--block-size): sharing works at block granularity")
        if args.mode != "stream":
            ap.error("--prefix-cache requires --mode stream")
        fam = get_config(args.arch).family
        if fam in ("ssm", "hybrid"):
            ap.error(f"--prefix-cache is unsupported for the {fam!r} "
                     f"family ({args.arch}): recurrent state is not "
                     "addressable by token-block chains")
        if fam in ("encdec", "vlm"):
            ap.error(f"--prefix-cache is unsupported for the {fam!r} "
                     f"family ({args.arch}): prompt KV depends on "
                     "per-request encoder/vision conditioning, so equal "
                     "token prefixes do not imply equal pages")
    if args.kv_quant != "none":
        if not args.block_size:
            ap.error("--kv-quant requires the paged arena (--block-size): "
                     "quantize-on-insert and in-kernel dequant live on "
                     "the paged block-table path; the contiguous slot "
                     "arena has no quantized read path")
        if args.mode != "stream":
            ap.error("--kv-quant requires --mode stream (the lockstep "
                     "batch path builds its own contiguous-arena engines)")
        fam = get_config(args.arch).family
        if fam in ("ssm", "hybrid"):
            ap.error(f"--kv-quant is unsupported for the {fam!r} family "
                     f"({args.arch}): recurrent state is a running "
                     "summary, not per-position KV pages — requantizing "
                     "it every step would compound rounding error")
        if fam == "encdec":
            ap.error(f"--kv-quant is unsupported for the {fam!r} family "
                     f"({args.arch}): cross-attention KV is written by "
                     "the one-time encoder pass, which bypasses the "
                     "quantize-on-insert path")
    if args.shared_prefix < 0:
        ap.error("--shared-prefix must be >= 0")
    # getattr: test helpers validate partial Namespaces without the
    # export flags.
    if (getattr(args, "metrics_out", None) or getattr(args, "trace", None)) \
            and args.mode != "stream":
        ap.error("--metrics-out/--trace require --mode stream (telemetry "
                 "instruments the continuous-batching step loop)")
    if args.paged_attn and not args.block_size:
        ap.error(f"--paged-attn {args.paged_attn} requires a paged arena "
                 "(--block-size); the contiguous slot arena has no block "
                 "tables to attend through")
    if args.spec == "off":
        if args.spec_k is not None:
            ap.error("--spec-k requires --spec {ngram,draft}")
        if args.spec_draft_model:
            ap.error("--spec-draft-model requires --spec draft")
    if args.spec == "draft":
        if not args.spec_draft_model:
            ap.error("--spec draft requires --spec-draft-model (e.g. "
                     "qwen3-0.6b); use --spec ngram for model-free "
                     "drafting")
        dfam = get_config(args.spec_draft_model).family
        if dfam in ("ssm", "hybrid", "encdec", "vlm"):
            ap.error(f"--spec-draft-model {args.spec_draft_model} "
                     f"({dfam!r} family) cannot draft: recurrent state "
                     "cannot roll back, and encoder/vision conditioning "
                     "cannot be supplied to a draft pass — use a "
                     "decoder-only draft model")
    if args.spec == "ngram" and args.spec_draft_model:
        ap.error("--spec-draft-model is only used by --spec draft")
    if args.spec != "off":
        if args.mode != "stream":
            ap.error("--spec requires --mode stream (the lockstep batch "
                     "path has no proposer/verifier)")
        fam = get_config(args.arch).family
        if fam in ("ssm", "hybrid"):
            ap.error(f"--spec is unsupported for the {fam!r} family "
                     f"({args.arch}): rejected tokens advance the "
                     "recurrent state, which cannot be rolled back")
        if args.spec_k is not None and args.spec_k < 1:
            ap.error(f"--spec-k must be >= 1, got {args.spec_k}")
        if args.chunk_size < 2:
            ap.error("--spec needs --chunk-size >= 2 (one committed-token "
                     "lane plus at least one proposal lane)")
    if args.dp < 1 or args.tp < 1:
        ap.error(f"--dp/--tp must be >= 1, got dp={args.dp} tp={args.tp}")
    if args.dp * args.tp > 1:
        if args.mode != "stream":
            ap.error("--dp/--tp require --mode stream (the lockstep batch "
                     "path builds unsharded engines)")
        ndev = jax.device_count()
        if args.dp * args.tp > ndev:
            ap.error(f"mesh dp={args.dp} x tp={args.tp} needs "
                     f"{args.dp * args.tp} devices but only {ndev} "
                     "visible (set XLA_FLAGS="
                     "--xla_force_host_platform_device_count=N to "
                     "emulate on CPU)")
        mcfg = get_config(args.arch)
        if args.reduced:
            mcfg = mcfg.reduced()
        _check_mesh_divisibility(ap, mcfg, args.arch, args)
        if args.dp > 1 and args.slots % args.dp:
            ap.error(f"--slots {args.slots} not divisible by --dp "
                     f"{args.dp}: each 'data' replica must own an equal "
                     "contiguous slot block")
        if args.spec == "draft" and args.tp > 1:
            dcfg = get_config(args.spec_draft_model)
            if args.reduced:
                dcfg = dcfg.reduced()
            _check_mesh_divisibility(ap, dcfg, args.spec_draft_model,
                                     args, role="draft model ")


def _check_mesh_divisibility(ap, cfg, arch: str, args,
                             role: str = "") -> None:
    """Refuse a tensor-parallel degree the architecture cannot shard
    evenly — an uneven head split would need padded shards and break
    token identity with the single-device run."""
    for what, n in (("kv-heads", cfg.num_kv_heads),
                    ("attention heads", cfg.num_heads),
                    ("vocab", cfg.vocab_size)):
        if n % args.tp:
            ap.error(f"--tp {args.tp} does not divide {role}{arch}'s "
                     f"{n} {what}; pick a tp that divides every "
                     "sharded axis (heads, kv-heads, vocab)")


def build_mesh(args):
    """``(data, model)`` device mesh for --dp/--tp, or None when both
    degrees are 1 (single-device serving, no GSPMD partitioning)."""
    if args.dp * args.tp == 1:
        return None
    return make_mesh((args.dp, args.tp), ("data", "model"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--quant", default="none",
                    choices=["none", "fp16", "q8_0", "q3_k_s", "q6_k"])
    ap.add_argument("--mode", default="stream", choices=["stream", "batch"])
    ap.add_argument("--chunk-size", type=int, default=8,
                    help="chunked prefill: prompt tokens per slot per "
                         "unified step (the step's traced width)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--batch", type=int, default=2,
                    help="batch mode: lockstep batch size")
    ap.add_argument("--requests", type=int, default=8,
                    help="stream mode: number of requests")
    ap.add_argument("--slots", type=int, default=4,
                    help="stream mode: KV arena slots")
    ap.add_argument("--block-size", type=int, default=0,
                    help="stream mode: paged KV arena block size in tokens "
                         "(0 = contiguous whole-sequence slots)")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="paged arena physical blocks "
                         "(0 = slots * ceil(max_seq/block_size))")
    ap.add_argument("--paged-attn", default=None,
                    choices=["fused", "ref"],
                    help="paged decode attention: fused block-table "
                         "Pallas kernel (default, O(live-token) KV "
                         "traffic) or the dense-gather oracle "
                         "(O(arena) traffic, differential reference); "
                         "requires a paged arena (--block-size)")
    ap.add_argument("--spec", default="off",
                    choices=["off", "ngram", "draft"],
                    help="speculative decoding: model-free prompt-lookup "
                         "n-gram proposer, or a small draft model "
                         "(--spec-draft-model), verified through the "
                         "unified chunked step")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="max proposal depth per slot per step (default "
                         "4, adaptively shrunk on low accept rates and "
                         "under token-budget pressure); requires --spec")
    ap.add_argument("--spec-draft-model", default=None,
                    help="draft model arch for --spec draft (e.g. "
                         "qwen3-0.6b drafting for qwen3-8b)")
    ap.add_argument("--kv-quant", default="none",
                    choices=["none", "int8"],
                    help="paged KV page storage: int8 codes + per-"
                         "(position, kv-head) fp16 scales, dequantized "
                         "inside the fused kernel's block-table walk "
                         "(~2x lower KV stream and arena residency); "
                         "requires --block-size")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="refcounted copy-on-write prefix sharing: map "
                         "cached prompt prefixes (full token blocks) onto "
                         "existing physical pages at admission instead of "
                         "re-prefilling and re-streaming them; requires "
                         "--block-size")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many common tokens to every "
                         "request (system-prompt workload — what "
                         "--prefix-cache deduplicates)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel degree: shard the slot axis (and "
                         "the paged arena's pages) over the mesh 'data' "
                         "axis; requires --slots divisible by dp")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard attention heads "
                         "and weight-stream tensors over the mesh "
                         "'model' axis; requires heads/kv-heads/vocab "
                         "divisible by tp")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "back2back"])
    ap.add_argument("--rate", type=float, default=8.0,
                    help="poisson arrival rate, requests/s")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--max-seq", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--offload-policy", action="store_true",
                    help="apply the Table-2 offload decision table to the "
                         "transfer ledger (host-resident kernels move 0 B)")
    ap.add_argument("--host-sampling", action="store_true",
                    help="ledger models llama.cpp-style host sampling "
                         "(full logit rows drained per step)")
    ap.add_argument("--metrics-out", default=None, metavar="FILE.jsonl",
                    help="write the per-step telemetry series (JSONL: "
                         "meta/admit/preempt/step/summary events — see "
                         "docs/observability.md) to this file")
    ap.add_argument("--trace", default=None, metavar="FILE.json",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(per-slot phase spans + ledger byte counter "
                         "tracks); open it at https://ui.perfetto.dev")
    args = ap.parse_args()
    validate_args(ap, args)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.mode == "stream":
        run_stream(cfg, model, params, args)
    else:
        run_batch(cfg, model, params, args)


if __name__ == "__main__":
    main()
