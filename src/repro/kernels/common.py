"""Shared front-end (format decode) and back-end (MAC) for all matmul kernels.

This file *is* the paper's architectural idea transplanted to TPU:

  * ``unpack_*`` — the decompress front-end (OP_CVT86 / OP_CVT53 analogs),
    running on the VPU: shift+mask int32 words into small integers, apply
    block scales, and emit a common dense representation.
  * ``mac_backend`` — the standardized multiply-accumulate back-end
    (SML8 + AD24 pipeline analog): one MXU contraction shared verbatim by
    every quantized kernel; f32 accumulation plays the role of the CGLA's
    24-bit accumulators.

Each format's kernel = (its own front-end) + (this one back-end), exactly
mirroring §III.C's "reconfigure diverse low-bit formats into a common
representation at the front-end, reuse the standardized back-end".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK_VALUE = -1e30


def unpack_words(words: jnp.ndarray, nbits: int) -> jnp.ndarray:
    """VPU bit-field decode: (..., W) int32 -> (..., W*32//nbits) int32."""
    per = 32 // nbits
    shifts = jnp.arange(per, dtype=jnp.int32) * nbits
    fields = jax.lax.shift_right_logical(
        words[..., None], jnp.broadcast_to(shifts, words.shape + (per,)))
    fields = fields & ((1 << nbits) - 1)
    return fields.reshape(*words.shape[:-1], -1)


def apply_block_scales(q: jnp.ndarray, eff_scale: jnp.ndarray,
                       sub: int) -> jnp.ndarray:
    """Scale integer quants (bn, bk) by per-``sub``-element scales
    (bn, bk//sub) -> dense float tile (bn, bk)."""
    bn, bk = q.shape
    w = q.astype(jnp.float32).reshape(bn, bk // sub, sub)
    w = w * eff_scale.reshape(bn, bk // sub, 1)
    return w.reshape(bn, bk)


def mac_backend(x_tile: jnp.ndarray, w_tile: jnp.ndarray,
                acc_ref, compute_dtype) -> None:
    """The one standardized MAC back-end: contract (bm,bk)x(bn,bk)->(bm,bn),
    accumulate in f32 (24-bit accumulator analog)."""
    acc_ref[...] += jax.lax.dot_general(
        x_tile.astype(compute_dtype), w_tile.astype(compute_dtype),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def start_of_k(acc_ref) -> None:
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)


def end_of_k(o_ref, acc_ref) -> None:
    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def interpret_kernels() -> bool:
    """The one backend decision for every Pallas kernel: compiled by
    Mosaic on a TPU, run through the Pallas interpreter only where the
    default backend is the CPU (tests, CPU rehearsals). Jitted programs
    read it once, where they are built, and pass it down."""
    return jax.default_backend() == "cpu"


def matmul_compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    target = (size + mult - 1) // mult * mult
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads)


def pick_block(size: int, preferred: int) -> int:
    """Largest divisor-friendly block <= preferred (sizes are pre-padded to
    powers-of-two-ish multiples by the wrappers)."""
    b = min(preferred, size)
    while size % b != 0:
        b //= 2
    return max(b, 1)
