"""Fig. 5–9 — per-kernel microbenchmarks.

For each of the four paper matmul kernels: interpret-mode wall time (CPU
oracle execution of the TPU kernel body), oracle agreement, and the
§III.B memory footprint claims (Q3_K ~4.5x smaller than FP16 at model
level). Plus the fused paged-attention decode kernel (PR 4): interpret-
mode wall time and gather-oracle agreement on a fragmented block table,
and the modeled per-step KV read bytes fused (live blocks only) vs the
dense gather (full table width) — the O(arena) -> O(live-token) win.

``--json PATH`` writes the metrics for CI artifact upload (wall-clock
microbench numbers are not regression-gated; the serving-level gated
metrics live in bench_serving.py).
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call, vs_paper
from repro.core.quant import pack
from repro.core.quant.formats import FORMATS
from repro.kernels import ops
from repro.kernels.paged_attention import paged_decode_attention
from repro.models import attention as attn

M, K, N = 16, 1024, 256

METRICS = {}


def quantized_matmuls() -> None:
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (M, K), jnp.float32)
    w = jax.random.normal(key, (N, K), jnp.float32) * 0.1
    for fmt in ["fp16", "q8_0", "q6_k", "q3_k"]:
        planes = pack.quantize(w, fmt)
        y_ref = ops.quantized_matmul(x, planes, fmt, impl="ref")
        us, y_pl = time_call(
            ops.quantized_matmul, x, planes, fmt, impl="pallas",
            interpret=True)
        err = float(jnp.max(jnp.abs(y_pl - y_ref)))
        emit(f"kernels/{fmt}/matmul_{M}x{K}x{N}", us,
             f"max_abs_err_vs_oracle={err:.2e} units={FORMATS[fmt].kernel_units}")
        METRICS[f"{fmt}_matmul_us"] = us
        METRICS[f"{fmt}_matmul_err"] = err
    # Memory footprint: Q3_K_S-style model (Q3_K linears) vs FP16.
    fp16_b = K * N * 2
    q3_b = pack.planes_nbytes(pack.quantize(w, "q3_k"))
    ratio_logical = 16.0 / FORMATS["q3_k"].logical_bpw
    emit("kernels/q3_k/memory_reduction_physical", 0.0,
         vs_paper(fp16_b / q3_b, 4.5))
    emit("kernels/q3_k/memory_reduction_logical", 0.0,
         vs_paper(ratio_logical, 4.5))


def paged_attention_bench() -> None:
    """Fused block-table decode kernel vs the ``paged_view`` gather
    oracle: wall time (interpret mode — the CPU oracle execution of the
    same kernel body CI serves with), agreement, and the modeled KV read
    bytes per step at a mostly-empty arena (live << capacity, the
    serving regime paging exists for)."""
    B, C, H, Hkv, D, bs, mb = 4, 4, 8, 2, 64, 16, 16
    nb = B * mb                              # table width 16 blocks/slot
    rng = np.random.RandomState(0)
    perm = rng.permutation(nb)
    tables = np.stack([perm[i * mb:(i + 1) * mb] for i in range(B)]) \
        .astype(np.int32)
    k_pages = jnp.asarray(rng.randn(1, nb + 1, Hkv, bs, D), jnp.float32)
    v_pages = jnp.asarray(rng.randn(1, nb + 1, Hkv, bs, D), jnp.float32)
    q = jnp.asarray(rng.randn(B, C, H, D), jnp.float32)
    pos0 = jnp.asarray([17, 3, 40, 9], jnp.int32)   # live depths << mb*bs
    sm = D ** -0.5

    us_f, out_f = time_call(
        paged_decode_attention, q, k_pages, v_pages, jnp.asarray(tables),
        pos0, sm_scale=sm, interpret=True)

    def gather_ref(q, kp, vp, tb, p0):
        kc = attn.paged_view(kp, tb)
        vc = attn.paged_view(vp, tb)
        pm = attn.decode_positions(p0, B, C)
        return attn.decode_attention(q, kc, vc, sm_scale=sm, kv_len=pm + 1)

    us_r, out_r = time_call(gather_ref, q, k_pages, v_pages,
                            jnp.asarray(tables), pos0)
    err = float(jnp.max(jnp.abs(out_f - out_r)))
    # Modeled per-step KV read traffic (f32 pages, k + v): the kernel
    # walks each slot's live blocks; the gather materializes every
    # slot's full table width.
    row_bytes = bs * Hkv * D * 4 * 2
    live = sum(min(int(p) + C - 1, mb * bs - 1) // bs + 1 for p in pos0)
    fused_rd = live * row_bytes
    ref_rd = B * mb * row_bytes
    emit(f"kernels/paged_attention/decode_{B}x{C}x{H}x{D}_bs{bs}", us_f,
         f"gather_ref_us={us_r:.1f} max_abs_err_vs_oracle={err:.2e} "
         f"kv_read_fused_KB={fused_rd/1e3:.1f} "
         f"kv_read_gather_KB={ref_rd/1e3:.1f} "
         f"(O(live) vs O(arena): {fused_rd/ref_rd:.3f})")
    METRICS["paged_attention_fused_us"] = us_f
    METRICS["paged_attention_gather_us"] = us_r
    METRICS["paged_attention_err"] = err
    METRICS["paged_attention_read_bytes_ratio"] = fused_rd / ref_rd


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduced", action="store_true",
                    help="reduced shapes (always on: this benchmark is "
                         "CPU-sized by construction)")
    ap.add_argument("--json", default="",
                    help="write the metrics JSON here (artifact upload; "
                         "not regression-gated)")
    args = ap.parse_args()
    quantized_matmuls()
    paged_attention_bench()
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"bench": "bench_kernels", "metrics": METRICS}, f,
                      indent=2, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
