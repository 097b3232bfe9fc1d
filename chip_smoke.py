#!/usr/bin/env python3
"""Bring-up smoke run of the serving main path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the dp=2 x tp=2 sharded path only

One chip: ``qwen3-1.7b`` at its published widths (28 layers, d_model
2048, 16 heads, 8 KV heads, head_dim 128, vocab 151936, bf16 weights
drawn from ``--seed``) is served through the normal path — ``build_model``
→ ``ServingEngine`` (paged arena, fused paged-attention kernel) →
``engine.serve`` — for 8 back-to-back requests of 128-512 prompt tokens
and 32 generated tokens each, 8 slots, chunk 32, 16-token blocks. It
checks one step compilation, every request complete, the kernel
compiled by Mosaic (a ``tpu_custom_call`` in the compiled step), and the
kernel against the dense gather oracle at the same widths.

Four chips: the same stream on one device and on a ('data', 'model') =
(2, 2) mesh over the four chips, in one process; it checks one step
compilation on each and reports token agreement and the largest logit
difference of one fixed model pass.

Any failed check exits non-zero. Without a TPU it exits non-zero and
names the platform it found; it never runs on the CPU. The last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-1.7b"
SLOTS, MAX_SEQ, CHUNK, BLOCK = 8, 2048, 32, 16
REQUESTS, PROMPT_MIN, PROMPT_MAX, GEN = 8, 128, 512, 32
# Fused kernel vs the gather oracle on the same bf16 pages, both in f32
# (oracle at "highest" matmul precision): the kernel's MXU passes may
# round its f32 operands to bf16 (~4e-3 relative), far below the O(1)
# error of a wrong page, head or mask.
KERNEL_TOL = 2e-2


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def requests(vocab: int, seed: int):
    import numpy as np
    from repro.runtime.request import Request
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    tokens=rng.randint(0, vocab,
                                       int(rng.randint(PROMPT_MIN,
                                                       PROMPT_MAX + 1))),
                    max_new_tokens=GEN) for i in range(REQUESTS)]


def engine_for(model, params, mesh=None):
    from repro.runtime.engine import ServingEngine
    return ServingEngine(model, params, num_slots=SLOTS, max_seq=MAX_SEQ,
                         chunk_size=CHUNK, block_size=BLOCK,
                         paged_attn="fused", mesh=mesh)


def serve_checked(engine, vocab: int, seed: int, label: str):
    """Serve the stream; require one step compile and every request
    complete with GEN in-vocabulary tokens."""
    t0 = time.perf_counter()
    report = engine.serve(requests(vocab, seed), seed=seed, realtime=False)
    wall = time.perf_counter() - t0
    done = [s for s in report.sequences if len(s.generated) == GEN]
    print(f"[{label}] step_compiles: {report.step_compiles}")
    print(f"[{label}] requests completed: {len(done)}/{REQUESTS} "
          f"with {GEN} tokens each")
    print(f"[{label}] smoke wall seconds (not a benchmark): {wall:.3f}")
    check(report.step_compiles == 1,
          f"{label}: step_compiles {report.step_compiles} != 1")
    check(len(report.sequences) == REQUESTS and len(done) == REQUESTS,
          f"{label}: {len(done)}/{REQUESTS} requests completed")
    check(all(0 <= t < vocab for s in done for t in s.generated),
          f"{label}: token id outside the vocabulary")
    return [list(map(int, s.generated)) for s in report.sequences]


def kernel_differential(cfg, seed: int) -> float:
    """One call of the compiled paged kernel against the gather oracle
    at the served widths; returns the max abs error."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention import paged_decode_attention
    from repro.models import attention as attn
    h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim()
    mb = MAX_SEQ // BLOCK
    rng = np.random.RandomState(seed + 1)
    tables = jnp.asarray(rng.permutation(SLOTS * mb)
                         .reshape(SLOTS, mb).astype(np.int32))
    pos0 = jnp.asarray(rng.randint(0, MAX_SEQ - CHUNK + 1, SLOTS), jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), 3)
    pages = (1, SLOTS * mb + 1, hkv, BLOCK, d)      # a one-layer stack
    kp = jax.random.normal(keys[0], pages, jnp.bfloat16)
    vp = jax.random.normal(keys[1], pages, jnp.bfloat16)
    q = jax.random.normal(keys[2], (SLOTS, CHUNK, h, d), jnp.bfloat16)
    out = paged_decode_attention(q, kp, vp, tables, pos0, sm_scale=d ** -0.5,
                                 out_dtype=jnp.float32, interpret=False)
    with jax.default_matmul_precision("highest"):
        ref = attn.decode_attention(
            q.astype(jnp.float32), attn.paged_view(kp, tables),
            attn.paged_view(vp, tables), sm_scale=d ** -0.5,
            kv_len=attn.decode_positions(pos0, SLOTS, CHUNK) + 1)
    return float(jnp.max(jnp.abs(out - ref.astype(jnp.float32))))


def one_chip(model, params, seed: int) -> None:
    cfg = model.cfg
    engine = engine_for(model, params)
    check(engine.interpret is False,
          "the backend decision interprets Pallas kernels on a TPU")
    t0 = time.perf_counter()
    compiled = engine.lower_step().compile()
    print(f"compile seconds (serving step): "
          f"{time.perf_counter() - t0:.3f}")
    has_kernel = "tpu_custom_call" in compiled.as_text()
    print(f"tpu_custom_call in compiled step: {has_kernel}")
    check(has_kernel, "the paged-attention kernel is not a Mosaic custom "
                      "call in the compiled serving step")
    del compiled
    serve_checked(engine, cfg.vocab_size, seed, "1 chip")
    err = kernel_differential(cfg, seed)
    print(f"paged kernel vs gather oracle: max abs err {err:.3e} "
          f"(tolerance {KERNEL_TOL})")
    check(err <= KERNEL_TOL, f"kernel differential {err} > {KERNEL_TOL}")


def fixed_pass_logits(engine, tokens):
    """Logits of one fixed chunk (positions 0..CHUNK-1, each slot on its
    own fresh blocks) through the engine's params and arena placement."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.parallel.sharding import activation_mesh
    nb = -(-CHUNK // BLOCK)
    tables = np.full((SLOTS, MAX_SEQ // BLOCK), engine.arena.null_block,
                     np.int32)
    tables[:, :nb] = np.arange(SLOTS * nb).reshape(SLOTS, nb)
    run = jax.jit(lambda p, t, c, tb: engine.model.decode_step(
        p, t, jnp.zeros((SLOTS,), jnp.int32), c,
        lengths=jnp.full((SLOTS,), CHUNK, jnp.int32), block_tables=tb,
        paged_impl="fused", interpret=engine.interpret)[0])
    with activation_mesh(engine.mesh):          # traced as the engine does
        logits = run(engine.params, jnp.asarray(tokens),
                     engine.arena.buffers, jnp.asarray(tables))
    return np.asarray(jax.device_get(logits), np.float32)


def four_chips(model, params, seed: int) -> None:
    import numpy as np
    from repro.launch.mesh import make_mesh
    vocab = model.cfg.vocab_size
    single = engine_for(model, params)
    mesh = make_mesh((2, 2), ("data", "model"))
    print(f"mesh: {dict(mesh.shape)} devices "
          f"{[d.id for d in mesh.devices.flat]}")
    sharded = engine_for(model, params, mesh=mesh)
    toks_1 = serve_checked(single, vocab, seed, "1 device")
    toks_4 = serve_checked(sharded, vocab, seed, "dp2 x tp2")
    same = sum(a == b for s1, s4 in zip(toks_1, toks_4)
               for a, b in zip(s1, s4))
    print(f"token agreement dp2 x tp2 vs 1 device: {same}/{REQUESTS * GEN} "
          f"({'identical' if toks_1 == toks_4 else 'DIVERGED'})")
    tokens = np.random.RandomState(seed + 3).randint(0, vocab,
                                                     (SLOTS, CHUNK))
    l1 = fixed_pass_logits(single, tokens)
    l4 = fixed_pass_logits(sharded, tokens)
    print(f"fixed-pass logits: max abs diff {float(np.abs(l1 - l4).max()):.6e}"
          f", argmax agreement {float((l1.argmax(-1) == l4.argmax(-1)).mean())}"
          f", bitwise identical {bool(np.array_equal(l1, l4))}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip serving path (default); 4: only "
                         "the dp=2 x tp=2 sharded path and its one-device "
                         "comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    try:
        import jax
        from repro.configs.registry import get_config
        from repro.launch.serve import use_compile_cache
        from repro.models.api import build_model
    except ImportError as e:
        fail(f"cannot import the serving package next to this script: {e}")
    devices = jax.devices()
    platform = devices[0].platform
    print(f"jax {jax.__version__}; devices: {devices}")
    check(platform == "tpu",
          f"no TPU found: JAX's platform is {platform!r}; this smoke run "
          f"never falls back to it")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} TPU devices, "
          f"found {len(devices)}")
    print(f"compilation cache: {use_compile_cache()}")
    cfg = get_config(ARCH)
    print(f"model {cfg.name}: layers {cfg.num_layers}, d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}, kv heads "
          f"{cfg.num_kv_heads}, head_dim {cfg.resolved_head_dim()}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}; bf16 random weights, "
          f"seed {args.seed}")
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    if args.chips == 1:
        one_chip(model, params, args.seed)
    else:
        four_chips(model, params, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
