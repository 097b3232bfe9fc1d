"""Unit tests for the serving-mesh sharding layer (in-process, single
device — the multi-device token differentials live in
``test_sharded_differential.py`` behind a forced-device subprocess).

Covers the exactness-preserving spec rules (weights out-feature over
'model', pages over 'data', MLA latents replicated), the trailing-None
normalization that keeps committed input shardings byte-identical to
GSPMD output shardings (the step_compiles == 1 contract), mesh
validation, per-device transfer-ledger closure, per-replica scheduler
stats, the mesh-keyed step-dtype probe cache, and the serve CLI's
fail-fast mesh flag matrix.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.registry import ARCHS, get_config
from repro.models.api import build_model
from repro.parallel import sharding as shard_rules
from repro.runtime.kvcache import (_STEP_DTYPE_CACHE, KVArena, PagedKVArena,
                                   step_leaf_dtypes)
from repro.runtime.request import Request, SamplingParams, Sequence
from repro.runtime.scheduler import Scheduler
from repro.runtime.speculative import DraftModelProposer
from repro.runtime.transfers import TransferLedger


def mesh_1x1():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


class FakeMesh:
    """Shape-only stand-in for a multi-device mesh: the serving *spec*
    rules read nothing but ``mesh.shape``/``axis_names``, so the rule
    logic is testable on one device (NamedSharding construction — which
    does need real devices — is covered by the forced-device
    differential subprocess)."""

    def __init__(self, dp, tp):
        self.shape = {"data": dp, "model": tp}
        self.axis_names = ("data", "model")


# ----------------------------------------------------------------------
# Spec rules
# ----------------------------------------------------------------------
def test_weight_spec_shards_out_features_over_model():
    m = FakeMesh(2, 2)
    assert shard_rules.serving_weight_spec(("layers", "wq"), (64, 32), m) \
        == P("model")
    assert shard_rules.serving_weight_spec(("layers", "w"), (4, 64, 32), m) \
        == P(None, "model")


def test_weight_spec_replicates_embed_router_and_vectors():
    m = FakeMesh(2, 2)
    assert shard_rules.serving_weight_spec(("embed",), (512, 128), m) == P()
    assert shard_rules.serving_weight_spec(("moe", "router"), (8, 128), m) \
        == P()
    assert shard_rules.serving_weight_spec(("norm", "g"), (128,), m) == P()


def test_weight_spec_replicates_indivisible_out_axis():
    assert shard_rules.serving_weight_spec(("w",), (63, 32),
                                           FakeMesh(2, 2)) == P()


def test_cache_spec_gqa_heads_and_pages():
    m = FakeMesh(2, 2)
    # head-major (L, pages, kv_heads, block, hd): pages over 'data',
    # heads over 'model'; the trailing feature axis is never sharded.
    assert shard_rules.serving_cache_spec(("k",), (4, 20, 2, 8, 32), m,
                                          paged=True) \
        == P(None, "data", "model")
    # int8 scale plane (L, pages, kv_heads, block): same head axis.
    assert shard_rules.serving_cache_spec(("k", "s"), (4, 20, 2, 8), m,
                                          paged=True) \
        == P(None, "data", "model")
    # Indivisible page count: pages replicate, heads still shard.
    assert shard_rules.serving_cache_spec(("k",), (4, 21, 2, 8, 32), m,
                                          paged=True) \
        == P(None, None, "model")
    # Per-slot storage (L, slots, seq, kv_heads, hd): head axis is -2.
    assert shard_rules.serving_cache_spec(("k",), (4, 20, 8, 2, 32), m) \
        == P(None, "data", None, "model")


def test_cache_spec_mla_latents_page_shard_only():
    m = FakeMesh(2, 2)
    # ckv/krope trailing axes are contraction dims — replicated.
    assert shard_rules.serving_cache_spec(("ckv",), (4, 20, 1, 8, 64), m,
                                          paged=True) == P(None, "data")
    assert shard_rules.serving_cache_spec(("krope",), (4, 20, 1, 8, 16), m,
                                          paged=True) == P(None, "data")


def test_specs_never_name_size_one_axes():
    """On a 1x1 (or dp=1 / tp=1) mesh every serving spec must replicate:
    GSPMD normalizes size-1 axes out of output shardings, so naming them
    on committed inputs doubles the executable cache (compiles == 2)."""
    m = FakeMesh(1, 1)
    assert shard_rules.serving_weight_spec(("w",), (64, 32), m) == P()
    assert shard_rules.serving_cache_spec(("k",), (4, 20, 2, 8, 32), m,
                                          paged=True) == P()
    assert shard_rules.slot_sharding(mesh_1x1(), 3).spec == P()
    # dp=1, tp=2: only the head axis is named, pages stay unnamed.
    m = FakeMesh(1, 2)
    assert shard_rules.serving_cache_spec(("k",), (4, 20, 2, 8, 32), m,
                                          paged=True) == P(None, None, "model")


def test_specs_never_carry_trailing_none():
    """GSPMD normalizes jit output specs trailing-None-free; committed
    inputs must match or the executable cache doubles (compiles == 2)."""
    m = FakeMesh(2, 2)
    for spec in (
            shard_rules.serving_weight_spec(("w",), (64, 32), m),
            shard_rules.serving_cache_spec(("k",), (4, 20, 2, 8, 32), m,
                                           paged=True),
            shard_rules.serving_cache_spec(("ckv",), (4, 20, 1, 8, 64), m,
                                           paged=True)):
        assert len(spec) == 0 or spec[-1] is not None


def test_serving_degrees_none_mesh():
    assert shard_rules.serving_degrees(None) == (1, 1)
    assert shard_rules.serving_degrees(mesh_1x1()) == (1, 1)


def test_validate_serving_mesh_rejects_unknown_axes():
    m = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("rows", "cols"))
    with pytest.raises(ValueError, match="unknown axes"):
        shard_rules.validate_serving_mesh(
            m, num_heads=4, num_kv_heads=2, vocab_size=512, num_slots=4)


def test_validate_serving_mesh_accepts_1x1():
    shard_rules.validate_serving_mesh(
        mesh_1x1(), num_heads=4, num_kv_heads=2, vocab_size=512,
        num_slots=3)


# ----------------------------------------------------------------------
# Per-device ledger accounting
# ----------------------------------------------------------------------
def _charged_ledger(dp, tp):
    cfg = get_config("qwen3-0.6b").reduced()
    led = TransferLedger(cfg, "q8_0", dp=dp, tp=tp)
    led.charge_step_weights(prefill_frac=0.25)
    led.charge_chunk("prefill", 8, 8)
    led.charge_chunk("decode", 1, 9)
    led.charge_sampled(2)
    led.charge("decode", "tables", "h2d", 4096)
    led.charge_cache_growth("decode", 1024)
    return led


def test_ledger_rejects_bad_degrees():
    cfg = get_config("qwen3-0.6b").reduced()
    with pytest.raises(ValueError, match="mesh degrees"):
        TransferLedger(cfg, "q8_0", dp=0, tp=2)


def test_ledger_per_device_closure():
    """Summing one device's bytes over the axis a category shards on
    recovers the mesh total exactly, cell by cell."""
    dp, tp = 2, 2
    led = _charged_ledger(dp, tp)
    total = led.breakdown()
    per_dev = led.per_device_breakdown()
    for phase, cats in total.items():
        for cat, by_dir in cats.items():
            shards = tp if cat == "weights" else dp
            for d, b in by_dir.items():
                assert per_dev[phase][cat][d] * shards == pytest.approx(b)
    assert led.per_device_weight_stream_bytes_per_token() * tp \
        == pytest.approx(led.weight_stream_bytes_per_token())


def test_ledger_single_device_views_degenerate():
    led = _charged_ledger(1, 1)
    assert led.per_device_bytes_per_token() \
        == pytest.approx(led.bytes_per_token())
    assert led.per_device_breakdown() == led.breakdown()


def test_ledger_aggregate_views_degree_invariant():
    """The mesh-total cells (and hence every committed bench baseline)
    must not move when dp/tp change — only the per_device views divide."""
    a, b = _charged_ledger(1, 1), _charged_ledger(4, 2)
    assert a.breakdown() == b.breakdown()
    assert a.bytes_per_token() == pytest.approx(b.bytes_per_token())


# ----------------------------------------------------------------------
# Scheduler per-replica stats
# ----------------------------------------------------------------------
def test_scheduler_rejects_indivisible_dp():
    with pytest.raises(ValueError, match="not divisible"):
        Scheduler(5, 64, dp=2)


def test_scheduler_replica_stats():
    sched = Scheduler(4, 64, dp=2)
    assert [sched.replica_of(s) for s in range(4)] == [0, 0, 1, 1]
    sched.active = {0: object(), 1: object(), 3: object()}
    sched.record_step()
    sched.active = {0: object()}
    sched.record_step()
    assert sched.stats.replica_occupancy_sums == [3.0, 1.0]
    assert sched.stats.replica_max_occupancy == [2, 1]
    assert sched.stats.replica_mean_occupancy == [1.5, 0.5]
    # Global tallies are unchanged by the per-replica split.
    assert sched.stats.occupancy_sum == 4.0
    assert sched.stats.max_occupancy == 3


# ----------------------------------------------------------------------
# Probe caches
# ----------------------------------------------------------------------
def test_step_dtype_cache_keys_on_mesh():
    """Two serving meshes must not share a probe entry, even though the
    abstract probe is layout-blind today."""
    cfg = ARCHS["mamba2-1.3b"].reduced()
    model = build_model(cfg)
    flags = tuple(KVArena.const_leaf_flags(model, 1, 16)) \
        if hasattr(KVArena, "const_leaf_flags") else None
    if flags is None:
        arena = KVArena(model, 1, 16)
        flags = arena._const_flags
    d1 = step_leaf_dtypes(model, 1, 16, jnp.bfloat16, flags, (1, 1))
    d2 = step_leaf_dtypes(model, 1, 16, jnp.bfloat16, flags, (2, 2))
    assert d1 == d2                      # layout-blind probe, same result
    keys = list(_STEP_DTYPE_CACHE[model])
    assert {(k[-1]) for k in keys} >= {(1, 1), (2, 2)}


def test_page_layout_reports_local_pages():
    cfg = get_config("qwen3-0.6b").reduced()
    model = build_model(cfg)
    arena = PagedKVArena(model, 2, 16, block_size=4, num_blocks=6)
    lay = arena.page_layout()
    assert lay["num_pages"] == 7
    assert lay["local_pages"] == 7       # no mesh: one shard owns all
    assert lay["data_shards"] == 1


# ----------------------------------------------------------------------
# Draft proposer: one dispatch per proposal round
# ----------------------------------------------------------------------
def test_draft_proposer_single_dispatch_per_round():
    """The catch-up feed and ALL k greedy rolls ride one jitted dispatch
    (chunked pass + lax.scan) — ``steps`` counts dispatches, so a round
    whose backlog fits one chunk costs exactly 1."""
    cfg = get_config("qwen3-0.6b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prop = DraftModelProposer(model, params, num_slots=2, max_seq=32,
                              chunk=4)
    seq = Sequence(Request(rid=0, tokens=np.arange(4) % cfg.vocab_size,
                           max_new_tokens=8,
                           sampling=SamplingParams(temperature=0.0)))
    seq.admit(0, 0.0)
    out = prop.propose({0: seq}, {0: 3})
    assert prop.steps == 1               # 1 dispatch, not 1 + (k-1)
    assert out[0].shape == (3,)
    # Next round: target committed the first proposal plus a bonus
    # token; the draft syncs and again needs exactly one dispatch.
    seq.start_decode()
    seq.record_token(int(out[0][0]), 0.0)
    seq.record_token(7, 0.0)
    prop.propose({0: seq}, {0: 3})
    assert prop.steps == 2


def test_draft_proposer_deep_backlog_pays_catchup_dispatches():
    """Only a committed backlog longer than one chunk (preemption
    re-admission) adds phase-1 catch-up dispatches."""
    cfg = get_config("qwen3-0.6b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prop = DraftModelProposer(model, params, num_slots=1, max_seq=64,
                              chunk=4)
    seq = Sequence(Request(rid=0, tokens=np.arange(10) % cfg.vocab_size,
                           max_new_tokens=4,
                           sampling=SamplingParams(temperature=0.0)))
    seq.admit(0, 0.0)
    prop.propose({0: seq}, {0: 2})
    # 10 pending: two chunked catch-up feeds (4 + 4) leave 2 for the
    # proposal dispatch -> 3 dispatches total.
    assert prop.steps == 3


# ----------------------------------------------------------------------
# serve CLI mesh flag matrix
# ----------------------------------------------------------------------
def _cli_args(**over):
    d = dict(arch="qwen3-0.6b", reduced=True, mode="stream", chunk_size=8,
             block_size=4, num_blocks=0, paged_attn=None, spec="off",
             spec_k=None, spec_draft_model=None, kv_quant="none",
             prefix_cache=False, shared_prefix=0, slots=4, dp=1, tp=1)
    d.update(over)
    return argparse.Namespace(**d)


def _expect_refusal(args, msg, capsys):
    from repro.launch import serve
    ap = argparse.ArgumentParser(prog="serve")
    with pytest.raises(SystemExit):
        serve.validate_args(ap, args)
    assert msg in capsys.readouterr().err


def test_cli_mesh_rejects_nonpositive_degrees(capsys):
    _expect_refusal(_cli_args(dp=0), "--dp/--tp must be >= 1", capsys)


def test_cli_mesh_rejects_batch_mode(capsys):
    _expect_refusal(_cli_args(tp=2, mode="batch", block_size=0),
                    "require --mode stream", capsys)


def test_cli_mesh_rejects_oversized_mesh(capsys):
    # The in-process test sees the real single CPU device, so any
    # dp*tp > 1 mesh must die on the device-count gate with the
    # force_host_platform hint.
    _expect_refusal(_cli_args(dp=2, tp=2),
                    "xla_force_host_platform_device_count", capsys)


def test_cli_mesh_single_device_passes():
    from repro.launch import serve
    ap = argparse.ArgumentParser(prog="serve")
    serve.validate_args(ap, _cli_args())
