"""Sharding rules for the production meshes (divisibility-guarded).

Mesh axes: ('data', 'model') single-pod 16x16; ('pod', 'data', 'model')
multi-pod 2x16x16. Batch shards over ('pod','data') (= DP/FSDP axes);
weights shard 2D over ('data','model') (FSDP x TP — GSPMD inserts the
per-layer all-gathers); KV caches shard batch over DP axes and *sequence*
over 'model' (flash-decoding style: GSPMD lowers the softmax/contraction
over the sharded sequence into the LSE-merge collective pattern, which is
how decode scales past num_kv_heads < axis size).

Every rule checks divisibility and falls back to replication on that dim —
this is what lets one rule set cover vocab 151936 and 49155, kv-heads 8 and
2, experts 256 and 40, batch 256 and 1.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_CACHE_SEQ_KEYS = {"k", "v", "ckv", "krope"}       # (..., B, S, ...) leaves
_CACHE_STATE_KEYS = {"conv", "ssm"}                # (..., B, ...) leaves


def dp_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, tuple):
        out = 1
        for n in name:
            out *= mesh.shape[n]
        return out
    return mesh.shape[name]


def _fits(dim: int, mesh: Mesh, axis) -> bool:
    return dim > 0 and dim % _axis_size(mesh, axis) == 0


# ----------------------------------------------------------------------
# Parameters
# ----------------------------------------------------------------------
def weight_spec(shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Generic 2D TPxFSDP rule: last dim -> 'data', the first suitable of
    (-2, -3) -> 'model'; 0/1-D params replicate."""
    if len(shape) < 2:
        return P()
    spec = [None] * len(shape)
    if _fits(shape[-1], mesh, "data"):
        spec[-1] = "data"
    for cand in (-2, -3):
        if len(shape) >= -cand and _fits(shape[cand], mesh, "model"):
            spec[cand] = "model"
            break
    return P(*spec)


def param_specs(abstract_params, mesh: Mesh):
    """Pytree of PartitionSpec matching the parameter pytree."""
    def walk(node):
        return jax.tree.map(lambda leaf: weight_spec(leaf.shape, mesh), node)
    return walk(abstract_params)


def param_shardings(abstract_params, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(abstract_params, mesh))


# ----------------------------------------------------------------------
# Batches (train/prefill inputs)
# ----------------------------------------------------------------------
def batch_spec(shape: Tuple[int, ...], mesh: Mesh) -> P:
    dp = dp_axes(mesh)
    spec = [None] * len(shape)
    if len(shape) >= 1 and _fits(shape[0], mesh, tuple(dp)):
        spec[0] = dp if len(dp) > 1 else dp[0]
    return P(*spec)


def batch_shardings(abstract_batch, mesh: Mesh):
    return jax.tree.map(
        lambda leaf: NamedSharding(mesh, batch_spec(leaf.shape, mesh)),
        abstract_batch)


# ----------------------------------------------------------------------
# KV / state caches (decode inputs)
# ----------------------------------------------------------------------
def cache_spec(path_key: str, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Caches are stacked (L, B, S, ...) or (L, B, ...): batch over DP,
    sequence over 'model' (flash-decoding)."""
    dp = dp_axes(mesh)
    spec = [None] * len(shape)
    if len(shape) >= 2 and _fits(shape[1], mesh, tuple(dp)):
        spec[1] = dp if len(dp) > 1 else dp[0]
    if path_key in _CACHE_SEQ_KEYS and len(shape) >= 3 and \
            _fits(shape[2], mesh, "model"):
        spec[2] = "model"
    return P(*spec)


def cache_shardings(abstract_cache, mesh: Mesh):
    def walk(node, key=""):
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        return NamedSharding(mesh, cache_spec(key, node.shape, mesh))
    return walk(abstract_cache)


# ----------------------------------------------------------------------
# Activation (residual-stream) constraint: sequence parallelism for train
# ----------------------------------------------------------------------
def activation_sharding(mesh: Mesh, seq_parallel: bool = True):
    """(B, S, D) residual constraint: batch over DP, seq over 'model'.
    Sequence parallelism keeps per-device activation memory (and remat
    checkpoints) 1/model_axis of the full sequence."""
    dp = dp_axes(mesh)
    dp_entry = dp if len(dp) > 1 else dp[0]
    if seq_parallel:
        return NamedSharding(mesh, P(dp_entry, "model", None))
    return NamedSharding(mesh, P(dp_entry, None, None))


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ----------------------------------------------------------------------
# Serving (inference) rules: exactness-preserving TP x slot-DP
# ----------------------------------------------------------------------
# The serving engine demands *token-identical* outputs versus a
# single-device run, so the rules below only ever shard axes that no
# reduction contracts over: weight out-features over 'model' (each shard
# computes its output slice with a full-extent contraction, then GSPMD
# all-gathers — pure data movement), and the slot/batch axis over 'data'
# (slots are independent). Partial-sum collectives (psum /
# reduce-scatter) never appear, because a float reassociation on a
# near-tie would flip sampled tokens.
#
# Deliberately replicated: embedding tables (the logits matmul output is
# re-gathered anyway and sampling reduces over vocab), MoE router gates
# (the router softmax normalizes over the expert axis), norm gains, and
# MLA latent cache pages (their trailing axes are rank/rope contraction
# dims, not heads).

_SERVING_REPLICATED_PARAM_KEYS = {"embed", "router"}
_GQA_CACHE_KEYS = {"k", "v"}                        # head axis shardable


def _trimmed(spec: List) -> P:
    """PartitionSpec with trailing Nones dropped.

    GSPMD normalizes jit *output* shardings to the trailing-None-free
    form; committed inputs must use the identical spelling or the jit
    executable cache treats step N+1's donated buffers as a new
    signature and compiles a second (bitwise-identical) executable."""
    while spec and spec[-1] is None:
        spec.pop()
    return P(*spec)


def _serving_fits(dim: int, mesh: Mesh, axis: str) -> bool:
    """Whether a serving rule may put ``axis`` on a ``dim``-sized array
    axis: divisible AND the mesh axis is real (size > 1). Naming a
    size-1 axis is semantically replication, but GSPMD normalizes it
    *away* in output shardings — the same committed-spelling mismatch
    ``_trimmed`` exists to prevent."""
    return _axis_size(mesh, axis) > 1 and _fits(dim, mesh, axis)


def serving_degrees(mesh: Optional[Mesh]) -> Tuple[int, int]:
    """(dp, tp) axis sizes of a ('data', 'model') serving mesh (1, 1 if None)."""
    if mesh is None:
        return 1, 1
    return mesh.shape.get("data", 1), mesh.shape.get("model", 1)


def validate_serving_mesh(mesh: Mesh, *, num_heads: int, num_kv_heads: int,
                          vocab_size: int, num_slots: int) -> None:
    """Fail fast on meshes the exact serving rules cannot honor.

    Raises ``ValueError`` when the mesh axes are not a subset of
    ``('data', 'model')``, when the mesh needs more devices than the
    backend exposes, or when head/vocab/slot counts do not divide the
    corresponding axis (replication would silently defeat the sharding
    the caller asked for, so refuse instead)."""
    extra = [a for a in mesh.axis_names if a not in ("data", "model")]
    if extra:
        raise ValueError(f"serving mesh axes must be ('data','model'); "
                         f"got unknown axes {extra}")
    dp, tp = serving_degrees(mesh)
    if dp * tp > jax.device_count():
        raise ValueError(f"mesh {dp}x{tp} needs {dp * tp} devices but only "
                         f"{jax.device_count()} are available")
    if tp > 1:
        if num_heads % tp:
            raise ValueError(f"num_heads={num_heads} not divisible by "
                             f"tp={tp}")
        if num_kv_heads % tp:
            raise ValueError(f"num_kv_heads={num_kv_heads} not divisible "
                             f"by tp={tp}")
        if vocab_size % tp:
            raise ValueError(f"vocab_size={vocab_size} not divisible by "
                             f"tp={tp}")
    if dp > 1 and num_slots % dp:
        raise ValueError(f"num_slots={num_slots} not divisible by dp={dp}")


def serving_weight_spec(path_keys: Tuple[str, ...], shape: Tuple[int, ...],
                        mesh: Mesh) -> P:
    """TP spec for one weight leaf: out-features (axis -2) over 'model'.

    Weights are stored ``(..., out, in)`` (quant planes keep the same
    leading out axis), so axis -2 is never contracted — sharding it is
    exact. Leaves under replicated param groups (embedding, MoE router),
    1-D leaves, and indivisible out axes replicate."""
    if len(shape) < 2 or any(k in _SERVING_REPLICATED_PARAM_KEYS
                             for k in path_keys):
        return P()
    if _serving_fits(shape[-2], mesh, "model"):
        spec = [None] * len(shape)
        spec[-2] = "model"
        return _trimmed(spec)
    return P()


def serving_param_shardings(params, mesh: Mesh):
    """NamedSharding pytree for serving params (see serving_weight_spec)."""
    def spec(path, leaf):
        keys = tuple(getattr(k, "key", str(k)) for k in path)
        return NamedSharding(mesh, serving_weight_spec(keys, leaf.shape, mesh))
    return jax.tree_util.tree_map_with_path(spec, params)


def serving_cache_spec(path_keys: Tuple[str, ...], shape: Tuple[int, ...],
                       mesh: Mesh, paged: bool = False) -> P:
    """Spec for one arena leaf: ``paged`` head-major pages ``(L, pages,
    heads, block, [dim])`` (code and int8-scale planes alike), else
    per-slot storage ``(L, slots, seq, heads, dim)``.

    Physical pages (or slots) shard over 'data' (each replica holds its
    slots' working set); GQA K/V leaves additionally shard the kv-head
    axis over 'model' (axis 2 of a page, axis -2 of a slot leaf). MLA
    latents (``ckv``/``krope``) carry rank/rope contraction axes, not
    heads, so they only page-shard."""
    spec = [None] * len(shape)
    if len(shape) >= 2 and _serving_fits(shape[1], mesh, "data"):
        spec[1] = "data"
    if any(k in _GQA_CACHE_KEYS for k in path_keys):
        head_ax = 2 if paged else len(shape) - 2
        if head_ax >= 2 and _serving_fits(shape[head_ax], mesh, "model"):
            spec[head_ax] = "model"
    return _trimmed(spec)


def serving_cache_shardings(buffers, mesh: Mesh, paged_flags=None):
    """NamedSharding pytree for arena buffers (see serving_cache_spec);
    ``paged_flags`` marks the flattened leaves that are paged."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(buffers)
    flags = paged_flags or (False,) * len(flat)
    out = [NamedSharding(mesh, serving_cache_spec(
               tuple(getattr(k, "key", str(k)) for k in path), leaf.shape,
               mesh, paged=f))
           for (path, leaf), f in zip(flat, flags)]
    return jax.tree_util.tree_unflatten(treedef, out)


def slot_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Per-slot operand sharding: leading slot axis over 'data' (fully
    replicated on a dp=1 mesh — see ``_serving_fits``)."""
    del ndim  # trailing Nones are dropped (see _trimmed)
    if _axis_size(mesh, "data") == 1:
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P("data"))


# ----------------------------------------------------------------------
# In-graph replication pins (the ONE constraint the step trace needs)
# ----------------------------------------------------------------------
# Committed input shardings (params, arena, slot operands) are enough
# for GSPMD to partition the unified step *bitwise-exactly* — the
# sharded differential suite pins token identity over DP, TP and
# combined meshes. Blanket per-layer-boundary constraints were tried
# and rejected: each ``with_sharding_constraint`` node moves XLA fusion
# boundaries and hence bf16 rounding, perturbing logits by ~1e-2 even
# when the requested layout is the one GSPMD already chose.
#
# The single exception is the MoE token path. When the flattened token
# axis arrives 'data'-sharded on a combined (dp>1, tp>1) mesh, the XLA
# CPU SPMD partitioner miscompiles the dispatch gather / expert einsum
# chain (wrong *values*, order-1 errors — not rounding). Pinning the
# flattened tokens fully replicated at MoE entry sidesteps the bad
# partitioning and is empirically fusion-neutral (bitwise-identical
# output on an unsharded run).
_ACTIVATION_CTX: List[Mesh] = []


@contextlib.contextmanager
def activation_mesh(mesh: Optional[Mesh]):
    """Scope under which ``constrain_replicated`` pins are live.

    The serving engine enters this around its step/draft *trace* (first
    call only; later calls hit the jit cache and the scope is a no-op).
    With no context — the default everywhere else — the pins are
    identity functions."""
    if mesh is None:
        yield
        return
    _ACTIVATION_CTX.append(mesh)
    try:
        yield
    finally:
        _ACTIVATION_CTX.pop()


def traced_serving_mesh() -> Optional[Mesh]:
    """The serving mesh of the step being traced (innermost
    ``activation_mesh`` scope), or None outside one."""
    return _ACTIVATION_CTX[-1] if _ACTIVATION_CTX else None


def paged_kernel_specs(mesh: Mesh, batch: int, num_heads: int,
                       num_kv_heads: int) -> Tuple[P, P, P, P]:
    """shard_map specs for the fused paged-attention kernel, which Mosaic
    cannot partition by itself: ``(per-slot (B, C, H, D), stacked pages
    (L, NP, Hkv, bs[, D]), tables/positions (B[, MB]), output)``.

    Slots split over 'data' and kv-heads (with their query heads) over
    'model' where they divide. Pages are whole on every 'data' shard:
    block tables hold global page ids and the allocator does not keep a
    slot's pages on its own replica, so a data-sharded arena is gathered
    for the kernel, one layer at a time (reads only; inserts stay GSPMD
    scatters)."""
    dp, tp = serving_degrees(mesh)
    data = "data" if dp > 1 and batch % dp == 0 else None
    model = "model" if tp > 1 and num_kv_heads % tp == 0 \
        and num_heads % tp == 0 else None
    per_slot = P(data, None, model)
    return per_slot, P(None, None, model), P(data), per_slot


def constrain_replicated(x):
    """Pin a traced intermediate fully replicated over the serving mesh.

    Used on the MoE flattened-token path, whose data-dependent
    dispatch gather the SPMD partitioner cannot split correctly (see
    module comment above). No-op outside an ``activation_mesh`` scope
    or on non-traced values."""
    if not _ACTIVATION_CTX or not isinstance(x, jax.core.Tracer):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_ACTIVATION_CTX[-1], P()))
