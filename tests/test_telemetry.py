"""Per-step serving telemetry tests: bit-exact ledger-delta closure
through the charge tap (including the preemption, speculative-rollback
and prefix-hit paths), streaming log-histogram accuracy against exact
nearest-rank quantiles, the serve loop's host phase spans, JSONL/Perfetto
export schema validity, and the observability contract — telemetry
on/off serves are token-identical with ONE step compile."""
import json
import math

import jax
import numpy as np
import pytest

from repro.configs.registry import ASSIGNED
from repro.models.api import build_model
from repro.runtime.engine import ServingEngine
from repro.runtime.request import Request
from repro.runtime.telemetry import (SERVE_PHASES, LogHistogram,
                                     StepTimeline, serve_report_lines, span,
                                     validate_chrome_trace,
                                     validate_metrics_jsonl)
from repro.runtime.transfers import TransferLedger

try:
    from hypothesis import given, settings
    from hypothesis import strategies as hyp_st
    HAVE_HYPOTHESIS = True
except ImportError:          # CI installs hypothesis; local dev may not
    HAVE_HYPOTHESIS = False


@pytest.fixture(scope="module")
def served_model():
    cfg = ASSIGNED["qwen3-0.6b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def make_requests(cfg, n, gen, seed=0, lo=4, hi=12, **kw):
    rng = np.random.RandomState(seed)
    return [Request(rid=i, tokens=rng.randint(0, cfg.vocab_size,
                                              int(rng.randint(lo, hi))),
                    max_new_tokens=gen, **kw) for i in range(n)]


def exact_nearest_rank(sorted_vals, q):
    return sorted_vals[max(int(math.ceil(q / 100 * len(sorted_vals))) - 1,
                           0)]


def hist_bound(h):
    """One geometric bin width, relative: the documented estimate error."""
    return 10.0 ** (1.0 / h.bins_per_decade) - 1.0


# ----------------------------------------------------------------------
# LogHistogram
# ----------------------------------------------------------------------
def test_histogram_empty_and_extremes():
    h = LogHistogram()
    assert h.count == 0 and h.percentile(50) == 0.0 and h.mean == 0.0
    h.record(0.0)                      # zero ITL gap -> underflow bin
    assert h.percentile(50) == 0.0     # clamped to observed min
    h.record(1e9)                      # beyond hi -> overflow bin
    assert h.percentile(99) == 1e9     # clamped to observed max
    assert h.count == 2


def test_histogram_percentile_accuracy_lognormal():
    rng = np.random.RandomState(3)
    vals = np.exp(rng.randn(5000) * 1.5 - 2.0)     # spans several decades
    h = LogHistogram()
    for v in vals:
        h.record(float(v))
    s = np.sort(vals)
    for q in (10, 50, 90, 99):
        exact = exact_nearest_rank(s, q)
        est = h.percentile(q)
        assert abs(est - exact) / exact <= hist_bound(h), \
            f"p{q}: est {est} vs exact {exact}"
    assert abs(h.mean - vals.mean()) / vals.mean() < 1e-9  # mean is exact


def test_histogram_merge_matches_union():
    rng = np.random.RandomState(5)
    a, b = LogHistogram(), LogHistogram()
    va = np.exp(rng.randn(400))
    vb = np.exp(rng.randn(300) + 1.0)
    for v in va:
        a.record(float(v))
    for v in vb:
        b.record(float(v))
    u = LogHistogram()
    for v in np.concatenate([va, vb]):
        u.record(float(v))
    a.merge(b)
    da, du = a.to_dict(), u.to_dict()
    # sum folds in a different order under merge (sum_a + sum_b vs the
    # interleaved union) — approximately equal; everything else exact
    assert da.pop("sum") == pytest.approx(du.pop("sum"), rel=1e-12)
    assert da == du
    with pytest.raises(ValueError):
        a.merge(LogHistogram(bins_per_decade=8))


def test_histogram_values_beyond_the_nominal_range():
    """Values at or above ``hi`` and below ``lo`` keep their own bins on
    the one geometric grid, so every rank reads within one bin width
    (once, everything at or above ``hi`` read back as the observed max:
    p10 of [1e5, 110070] read 110070)."""
    cases = [[100000.0, 110070.0], [1e5, 1.5e5, 3e5, 7e8],
             [1e-9, 3e-8, 5e-7, 2e-6], [2e-7] * 3 + [1e5, 4e6]]
    for vals in cases:
        h = LogHistogram()
        for v in vals:
            h.record(v)
        s = sorted(vals)
        for q in (10, 25, 50, 75, 90, 99):
            exact = exact_nearest_rank(s, q)
            assert abs(h.percentile(q) - exact) <= exact * hist_bound(h), \
                (vals, q, h.percentile(q))
    h = LogHistogram()
    for v in (1e-9, 1e300):                 # no finite value overflows
        h.record(v)
    assert h.percentile(10) == 1e-9 and h.percentile(99) == 1e300


def test_histogram_dict_roundtrip():
    h = LogHistogram()
    for v in (1e-9, 0.003, 0.5, 2.0, 7e6):
        h.record(v)
    h2 = LogHistogram.from_dict(json.loads(json.dumps(h.to_dict())))
    assert h2.to_dict() == h.to_dict()
    for q in (50, 90, 99):
        assert h2.percentile(q) == h.percentile(q)


if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(hyp_st.lists(hyp_st.floats(min_value=1e-7, max_value=1e6,
                                      allow_nan=False, allow_infinity=False),
                        min_size=1, max_size=200),
           hyp_st.sampled_from([10, 50, 90, 99]))
    def test_histogram_accuracy_property(vals, q):
        """Estimate within one relative bin width of the exact
        nearest-rank quantile, for arbitrary positive samples."""
        h = LogHistogram()
        for v in vals:
            h.record(v)
        exact = exact_nearest_rank(sorted(vals), q)
        assert abs(h.percentile(q) - exact) <= exact * (hist_bound(h)
                                                        + 1e-12)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_histogram_accuracy_property():
        """Placeholder keeping the property test visible when skipped."""


# ----------------------------------------------------------------------
# Charge tap closure (synthetic, no model)
# ----------------------------------------------------------------------
def test_tap_closure_synthetic():
    cfg = ASSIGNED["qwen3-0.6b"].reduced()
    led = TransferLedger(cfg, "none")
    tl = StepTimeline(led)
    rng = np.random.RandomState(7)
    t = 0.0
    for step in range(6):
        led.charge_step_weights(prefill_frac=0.5)
        led.charge_chunk("prefill", 4, int(rng.randint(4, 40)))
        led.charge_chunk("decode", 1, int(rng.randint(4, 40)))
        led.charge_sampled(2)
        led.charge_cache_growth("decode", float(rng.randint(1, 9999)))
        tl.record_step(t_start=t, t_end=t + 0.01, occupancy=2, compiles=0,
                       counters={"steps": step + 1}, gauges={}, slots=[])
        t += 0.01
    led.charge_sampled(1)             # trailing charge after last step
    tl.finalize(t)
    assert tl.ledger_delta_totals() == led.breakdown()   # EXACT equality
    # the trailing charge landed in finalize()'s zero-duration event
    assert tl.events[-1].wall_s == 0.0
    # and per-step deltas partition the totals cell-by-cell
    cells = {}
    for ev in tl.events:
        for k, v in ev.ledger_delta.items():
            cells[k] = cells.get(k, 0.0) + v
    for k, v in led.flat_cells().items():
        assert cells[k] == pytest.approx(v, rel=1e-9)


def test_tap_is_exclusive_and_detachable():
    cfg = ASSIGNED["qwen3-0.6b"].reduced()
    led = TransferLedger(cfg, "none")
    StepTimeline(led)
    with pytest.raises(RuntimeError):
        led.attach_tap(lambda *a: None)
    led.detach_tap()
    led.attach_tap(lambda *a: None)   # fresh attach after detach is fine


# ----------------------------------------------------------------------
# Engine integration
# ----------------------------------------------------------------------
def test_token_identity_compiles_and_closure(served_model):
    cfg, model, params = served_model
    mk_eng = lambda tel: ServingEngine(model, params, num_slots=3,
                                       max_seq=20, chunk_size=6,
                                       telemetry=tel)
    r_off = mk_eng(False).serve(make_requests(cfg, 5, 4, seed=1), seed=0,
                                realtime=False)
    r_on = mk_eng(True).serve(make_requests(cfg, 5, 4, seed=1), seed=0,
                              realtime=False)
    assert r_off.timeline is None and r_on.timeline is not None
    for a, b in zip(r_off.sequences, r_on.sequences):
        assert a.generated == b.generated
    assert r_on.step_compiles == 1
    tl = r_on.timeline
    # spans on: every step carries its host phases, and nothing else moved
    assert tl.events and all(set(ev.spans) == set(SERVE_PHASES)
                             for ev in tl.events)
    assert tl.ledger_delta_totals() == r_on.ledger.breakdown()
    assert sum(ev.counters.get("decode_tokens", 0) for ev in tl.events) \
        == r_on.stats.decode_tokens
    # every step carries exactly the jit activity the engine observed
    assert sum(ev.compiles for ev in tl.events) == r_on.step_compiles


def test_closure_under_preemption_and_prefix_hits(served_model):
    """The tap must close through the stressful paths: block exhaustion
    preempting sequences (recompute re-charges prompt chunks) and warm
    prefix-cache admissions (mapped pages charge nothing)."""
    cfg, model, params = served_model
    eng = ServingEngine(model, params, num_slots=4, max_seq=24,
                        chunk_size=4, block_size=4, num_blocks=13,
                        paged_attn="fused", prefix_cache=True,
                        telemetry=True)
    rng = np.random.RandomState(21)
    shared = rng.randint(0, cfg.vocab_size, 12)
    mk = lambda: [Request(rid=i, tokens=np.concatenate(
        [shared, rng.randint(0, cfg.vocab_size, 2)]),
        max_new_tokens=4) for i in range(6)]
    r_cold = eng.serve(mk(), seed=0, realtime=False)
    # the cold run exhausts the 13-block arena: preemption re-charges
    # recomputed prompt chunks through the tap and must still close
    assert r_cold.sched.preemptions > 0
    ctl = r_cold.timeline
    assert ctl.ledger_delta_totals() == r_cold.ledger.breakdown()
    assert sum(ev.counters.get("preemptions", 0) for ev in ctl.events) \
        == r_cold.sched.preemptions
    r_warm = eng.serve(mk(), seed=0, realtime=False)
    tl = r_warm.timeline
    assert tl.ledger_delta_totals() == r_warm.ledger.breakdown()
    tot = lambda k: sum(ev.counters.get(k, 0) for ev in tl.events)
    assert tot("prefix_hits") == r_warm.stats.prefix.hits > 0
    assert tot("prefix_hit_tokens") == r_warm.stats.prefix.hit_tokens


def test_closure_under_speculative_rollback(served_model):
    """Verify-step rollbacks (rejected lanes already charged their KV
    stream) and the draft model's second ledger account both close."""
    cfg, model, params = served_model
    rng = np.random.RandomState(11)
    pat = rng.randint(0, cfg.vocab_size, 4)
    reqs = [Request(rid=i, tokens=np.tile(pat, 2), max_new_tokens=24)
            for i in range(3)]
    eng = ServingEngine(model, params, num_slots=3, max_seq=40,
                        chunk_size=8, spec="ngram", spec_k=4,
                        telemetry=True)
    rep = eng.serve(reqs, seed=0, realtime=False)
    tl = rep.timeline
    assert tl.ledger_delta_totals() == rep.ledger.breakdown()
    tot = lambda k: sum(ev.counters.get(k, 0) for ev in tl.events)
    assert tot("spec_proposed") == rep.stats.spec.proposed > 0
    assert tot("spec_accepted") == rep.stats.spec.accepted > 0
    assert tot("spec_rolled_back") == rep.stats.spec.rolled_back


def test_exports_validate_and_bottleneck(served_model, tmp_path):
    """Both exports carry the host phase spans and pass their validators
    (the name predates the spans: the exports once carried a modeled
    transfer-bound verdict per step)."""
    cfg, model, params = served_model
    eng = ServingEngine(model, params, num_slots=2, max_seq=16,
                        chunk_size=4, telemetry=True)
    rep = eng.serve(make_requests(cfg, 4, 3, seed=2, hi=9), seed=0,
                    realtime=False)
    tl = rep.timeline
    mpath, tpath = tmp_path / "m.jsonl", tmp_path / "t.json"
    tl.write_metrics_jsonl(str(mpath))
    tl.write_chrome_trace(str(tpath))
    assert validate_metrics_jsonl(str(mpath)) == len(tl.events)
    assert validate_chrome_trace(str(tpath)) > 0

    lines = [json.loads(ln) for ln in mpath.read_text().splitlines()]
    assert lines[0]["event"] == "meta"
    assert lines[-1]["event"] == "summary"
    assert "bottleneck" not in lines[-1]
    steps = [ln for ln in lines if ln["event"] == "step"]
    # JSONL step deltas re-sum to the ledger totals (serialized floats)
    tot = {}
    for s in steps:
        for k, v in s["ledger_delta"].items():
            tot[k] = tot.get(k, 0.0) + v
    flat = {"/".join(k): v for k, v in rep.ledger.flat_cells().items()}
    assert set(tot) == set(k for k, v in flat.items() if v)
    for k, v in tot.items():
        assert v == pytest.approx(flat[k], rel=1e-9)
    # every step line carries its phases, as recorded
    assert [s["spans"] for s in steps] == \
        [{n: list(t) for n, t in ev.spans.items()} for ev in tl.events]
    assert all(set(s["spans"]) == set(SERVE_PHASES) for s in steps)

    trace = json.loads(tpath.read_text())
    assert isinstance(trace["traceEvents"], list)
    ts = [e["ts"] for e in trace["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts)
    assert any(e["ph"] == "C" for e in trace["traceEvents"])
    # each phase is a span on the engine step track; dispatch and sync
    # nest inside their step's span
    phases = [e for e in trace["traceEvents"] if e.get("cat") == "phase"]
    assert len(phases) == len(SERVE_PHASES) * len(tl.events)
    assert all(e["tid"] == 0 and e["dur"] > 0 for e in phases)
    step_box = {int(e["name"].split()[1]): e for e in trace["traceEvents"]
                if e.get("cat") == "step"}
    for e in phases:
        if e["name"] in ("serve.dispatch", "serve.sync"):
            box = step_box[e["args"]["step"]]
            assert box["ts"] <= e["ts"] and \
                e["ts"] + e["dur"] <= box["ts"] + box["dur"] + 1e-6


def test_validator_rejects_overlapping_spans(served_model, tmp_path):
    cfg, model, params = served_model
    eng = ServingEngine(model, params, num_slots=2, max_seq=16,
                        chunk_size=4, telemetry=True)
    rep = eng.serve(make_requests(cfg, 2, 2, seed=3, hi=9), seed=0,
                    realtime=False)
    ev = rep.timeline.events[0]
    a, b = ev.spans["serve.sync"]
    ev.spans["serve.bookkeep"][0] = a        # starts inside sync
    path = tmp_path / "m.jsonl"
    rep.timeline.write_metrics_jsonl(str(path))
    with pytest.raises(ValueError, match="overlap"):
        validate_metrics_jsonl(str(path))


def test_step_events_carry_disjoint_ordered_phases(served_model):
    """Every step of a serve carries the five step phases, disjoint and
    in order inside their iteration, with the step's t_start/t_end equal
    to the dispatch's start and the sync's end; a span outside the engine
    records on the timeline it is given."""
    cfg, model, params = served_model
    eng = ServingEngine(model, params, num_slots=3, max_seq=24,
                        chunk_size=4, block_size=4, num_blocks=20,
                        paged_attn="fused", telemetry=True)
    rep = eng.serve(make_requests(cfg, 5, 4, seed=8), seed=0,
                    realtime=False)
    tl = rep.timeline
    assert len(tl.events) == rep.stats.steps > 0
    prev_end = 0.0
    for ev in tl.events:
        assert list(ev.spans) == list(SERVE_PHASES)
        ivs = [ev.spans[n] for n in SERVE_PHASES]
        for (a0, a1), (b0, b1) in zip(ivs, ivs[1:]):
            assert a0 <= a1 <= b0 <= b1
        assert ivs[0][0] >= prev_end              # after the last step
        prev_end = ivs[-1][1]
        assert ev.t_start == ev.spans["serve.dispatch"][0]
        assert ev.t_end == ev.spans["serve.sync"][1]
    assert set(tl.phase_ms()) == set(SERVE_PHASES)

    led = TransferLedger(cfg, "none")
    own = StepTimeline(led)
    own.origin = 10.0
    with span("custom.phase", own) as rec:
        pass
    assert rec.t0 <= rec.t1
    assert own._spans["custom.phase"] == [rec.t0 - 10.0, rec.t1 - 10.0]
    with span("custom.phase") as rec:        # no timeline: trace only
        pass
    assert rec.t1 >= rec.t0


def test_serve_report_lines_smoke(served_model):
    cfg, model, params = served_model
    eng = ServingEngine(model, params, num_slots=2, max_seq=16,
                        chunk_size=4, telemetry=True)
    rep = eng.serve(make_requests(cfg, 3, 3, seed=4, hi=9), seed=0,
                    realtime=False)
    text = "\n".join(serve_report_lines(eng, rep, total_requests=3))
    for needle in ("step compiles", "mean queue wait", "host phases",
                   "p50", "bookkeep"):
        assert needle in text, f"report lines missing {needle!r}"


def test_queue_wait_accounting(served_model):
    cfg, model, params = served_model
    eng = ServingEngine(model, params, num_slots=2, max_seq=16,
                        chunk_size=4, telemetry=True)
    rep = eng.serve(make_requests(cfg, 5, 3, seed=6, hi=9), seed=0,
                    realtime=False)
    tl = rep.timeline
    assert tl.hists["queue_wait_s"].count == rep.sched.admitted
    assert rep.sched.mean_queue_wait >= 0.0
    assert tl.hists["ttft_s"].count == rep.sched.completed
