"""Operations and bytes that the algorithm needs, from shapes alone.

These count what serving a token *requires*, never what an
implementation chooses to do: padded chunk lanes, idle slots and logits
of positions nobody samples cost nothing here. So the same work is
counted whatever implements it, and a change that removes waste shows
as a higher share of the peak.

``m`` is a dict of model sizes (``L``, ``d``, ``H``, ``Hkv``, ``hd``,
``ff``, ``V``) as the architecture's reference module gives them.
"""
from __future__ import annotations

from typing import Dict, Tuple

KV_BYTES = 2        # bfloat16 pages
ACT_BYTES = 2       # bfloat16 queries and attention outputs


def linear_params(m: Dict[str, int]) -> int:
    """Matmul weights of all decoder layers (embedding and head
    excluded): q, k, v, o and the three SwiGLU matrices."""
    d, hd = m["d"], m["hd"]
    attn = d * m["H"] * hd * 2 + d * m["Hkv"] * hd * 2
    return m["L"] * (attn + 3 * d * m["ff"])


def causal_pairs(ctx: int, fed: int) -> int:
    """(query, key) pairs of ``fed`` new tokens after ``ctx`` cached
    ones under a causal mask: sum over i < fed of (ctx + i + 1)."""
    return fed * ctx + fed * (fed + 1) // 2


def model_flops(m: Dict[str, int], ctx: int, fed: int, sampled: int) -> int:
    """Useful FLOPs of feeding ``fed`` tokens to one sequence that has
    ``ctx`` tokens cached, sampling ``sampled`` of them: 2 per linear
    weight per token, 4 * H * hd per causal (query, key) pair per layer
    (scores and weighted values), and the head for sampled rows only."""
    return (2 * linear_params(m) * fed
            + 4 * m["L"] * m["H"] * m["hd"] * causal_pairs(ctx, fed)
            + 2 * m["d"] * m["V"] * sampled)


def paged_attention_work(m: Dict[str, int], ctx: int,
                         fed: int) -> Tuple[int, int]:
    """(FLOPs, HBM bytes) that decode attention over a paged cache needs
    for one sequence in one step, over all layers: the causal score and
    value products, and one read of the live K and V pages (``ctx +
    fed`` positions, every kv-head) plus the queries in and the outputs
    out."""
    flops = 4 * m["L"] * m["H"] * m["hd"] * causal_pairs(ctx, fed)
    kv = (ctx + fed) * m["Hkv"] * m["hd"] * 2 * KV_BYTES
    qo = 2 * fed * m["H"] * m["hd"] * ACT_BYTES
    return flops, m["L"] * (kv + qo)


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bytes_per_s: float) -> float:
    """The least time a chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak_flops, nbytes / peak_bytes_per_s)
