"""The arithmetic metric readers share: exact percentiles, the replay of
step events into per-sequence work, tokens kept in the window, and the
device shares of a traced window."""
import math

import numpy as np
import pytest

from bench import counters
from bench.measure import RequestRecord, RunData, TracedWindow, percentile

M = {"L": 2, "d": 8, "H": 4, "Hkv": 2, "hd": 2, "ff": 16, "V": 100}
PEAKS = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e3}


def test_percentile_matches_numpy_and_keeps_misses():
    xs = list(np.random.default_rng(0).normal(size=57))
    for q in (50, 90, 95, 99):
        assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert percentile([1.0, 2.0, math.inf], 50) == 2.0
    assert percentile([1.0, 2.0, math.inf], 95) == math.inf
    assert percentile([], 95) is None


def run_with(steps, preemptions=(), traced=None, seconds=1.0):
    reqs = [RequestRecord(rid=r, arrival_s=0.0, prompt_len=6,
                          max_new_tokens=3, greedy=True, n_out=3,
                          t_first_token=0.1, t_done=0.3) for r in (0, 1)]
    return RunData(seconds=seconds, setup_s=1.0, chips=1, dims=M,
                   peaks=PEAKS, requests=reqs, steps=steps,
                   admissions=[(0, 0.0), (1, 0.0)],
                   preemptions=list(preemptions), traced=traced)


# rid 0: prompt of 6 in chunks 4 + 2 (the second samples), then decodes;
# rid 1: chunk of 4, preempted, restarts from nothing.
STEPS = [
    (0.0, 0.1, [(0, 0, "prefill", 4, 0), (1, 1, "prefill", 4, 0)]),
    (0.1, 0.2, [(0, 0, "prefill", 2, 1)]),
    (0.3, 0.4, [(0, 0, "decode", 1, 1), (1, 1, "prefill", 4, 0)]),
    (0.5, 1.5, [(0, 0, "decode", 1, 1), (1, 1, "prefill", 2, 1)]),
]


def test_replay_rebuilds_context_and_restarts_on_preemption():
    work = run_with(STEPS, preemptions=[(1, 0.25)]).replay()
    got = [(w.step, w.rid, w.ctx, w.fed, w.sampled) for w in work]
    assert got == [(0, 0, 0, 4, 0), (0, 1, 0, 4, 0), (1, 0, 4, 2, 1),
                   (2, 0, 6, 1, 1), (2, 1, 0, 4, 0), (3, 0, 7, 1, 1),
                   (3, 1, 4, 2, 1)]


def test_window_tokens_keep_only_what_survived_and_ended_in_window():
    steps = [(0.0, 0.1, [(1, 1, "prefill", 4, 1)]),
             (0.1, 0.2, [(1, 1, "decode", 1, 1), (0, 0, "prefill", 4, 1)]),
             (0.3, 0.4, [(1, 1, "prefill", 4, 1)]),
             (0.5, 1.5, [(0, 0, "decode", 1, 1)])]
    # rid 1's first two tokens were undone by its preemption at 0.25;
    # rid 0's last token came after the window closed.
    r = run_with(steps, preemptions=[(1, 0.25)])
    assert r.window_tokens() == 2
    assert r.window_preemptions() == 1
    assert r.mean_step_ms() == pytest.approx(100.0)


def test_device_shares_of_a_traced_window():
    tw = TracedWindow(t0_s=0.0, t1_s=0.45, window_s=0.5,
                      busy_s={"/device:TPU:0": 0.4},
                      op_s={"/device:TPU:0": {"paged_decode_attention.1":
                                              0.01, "fusion": 0.3}},
                      gaps=[])
    r = run_with(STEPS, preemptions=[(1, 0.25)], traced=tw)
    assert r.idle_pct() == pytest.approx(20.0)
    work = [w for w in r.replay() if w.step in (0, 1, 2)]   # mids < 0.45
    flops = sum(counters.model_flops(M, w.ctx, w.fed, w.sampled)
                for w in work)
    assert r.mfu_pct() == pytest.approx(100 * flops / (0.5 * 1e6))
    need = 0.0
    for k in (0, 1, 2):
        f = b = 0
        for w in work:
            if w.step == k:
                wf, wb = counters.paged_attention_work(M, w.ctx, w.fed)
                f, b = f + wf, b + wb
        need += max(f / 1e6, b / 1e3)
    assert r.kernel_roofline_pct("paged_decode_attention") == \
        pytest.approx(100 * need / 0.01)
    assert r.kernel_roofline_pct("no_such_kernel") is None
    assert run_with(STEPS).mfu_pct() is None       # untraced: silent


def test_itl_takes_every_gap_and_restarts_on_preemption():
    # rid 1's first two tokens are undone by its preemption at 0.35 and
    # emitted again after it.
    steps = [(0.0, 0.1, [(0, 0, "prefill", 4, 1), (1, 1, "prefill", 4, 1)]),
             (0.1, 0.3, [(0, 0, "decode", 1, 1), (1, 1, "decode", 1, 1)]),
             (0.4, 0.5, [(1, 1, "prefill", 4, 1)]),
             (0.5, 0.6, [(0, 0, "decode", 1, 1), (1, 1, "decode", 1, 1)]),
             (0.6, 0.9, [(1, 1, "decode", 1, 1)])]
    r = run_with(steps, preemptions=[(1, 0.35)])
    assert r.emissions() == {0: [0.1, 0.3, 0.6], 1: [0.5, 0.6, 0.9]}
    assert r.itl_ms() == pytest.approx([200.0, 300.0, 100.0, 300.0])
    assert r.window_tokens() == 6
    r.requests[1].t_done = None                 # never completed
    assert r.itl_ms()[2:] == [math.inf, math.inf]


def test_stalls_are_the_long_intervals_between_step_ends():
    steps = [(0.0, 0.1, []), (0.1, 0.2, []), (0.9, 1.0, []),
             (1.0, 1.1, [])]
    r = run_with(steps, seconds=2.0)
    assert r.stalls(min_ms=500) == [(0.2, 1.0, pytest.approx(100.0))]
    s = r.step_summary()
    assert s["steps"] == 4 and s["stalls"] == 1
    assert s["longest"] == [[0.2, 800.0, 100.0]]
