"""Host phases and device scopes reduced: phase means outside the traced
stretch, the op-to-scope map (hand-made HLO text and a CPU-compiled
engine step), per-scope device time, idle time split by host phase (hand
-made events and the recorded v5e trace), the five readings on a
synthetic run, and the program's phases reaching a profiler trace."""
import math
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import spans, trace
from bench.measure import RequestRecord, RunData, TracedWindow
from bench.trace import Event

DATA = Path(__file__).parent / "data" / "tiny.xplane.pb"


def sp(t, sched=0.001, ops=0.002, disp=0.003, sync=0.1, book=0.004):
    """One iteration's phases starting at ``t`` (seconds)."""
    out, a = {}, t
    for name, d in zip(spans.PHASES[:5], (sched, ops, disp, sync, book)):
        out[name] = [a, a + d]
        a += d
    return out


def steps_of(phases):
    return [(p["serve.dispatch"][0], p["serve.sync"][1], []) for p in phases]


def test_phase_ms_means_per_step_outside_the_traced_stretch():
    phases = [sp(0.0), sp(0.2, sched=0.005), sp(0.4, sched=0.009),
              sp(1.5)]
    st = steps_of(phases)
    # the last step ends after the window: left out
    assert spans.phase_ms(st, phases, 1.0, ["serve.schedule"]) == \
        pytest.approx((1 + 5 + 9) / 3)
    # the second step touches the traced stretch: left out
    assert spans.phase_ms(st, phases, 1.0, ["serve.schedule"],
                          traced=(0.25, 0.3)) == pytest.approx(5.0)
    assert spans.phase_ms(st, phases, 1.0,
                          ["serve.operands", "serve.bookkeep"]) == \
        pytest.approx(6.0)
    # a program without spans: nothing to read
    assert spans.phase_ms(st, [{}] * 4, 1.0, ["serve.schedule"]) is None


HLO = """HloModule jit_step

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %exponential.3 = f32[4]{0} exponential(%p), metadata={op_name="jit(step)/sample/exp" stack_frame_id=3}
}

ENTRY %main {
  %gather.2 = bf16[4,8]{1,0} gather(%a, %b), metadata={op_name="jit(step)/embed/gather"}
  %custom-call.7 = bf16[4,8]{1,0} custom-call(%gather.2), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step)/layers/while/body/closed_call/jit(paged_decode_attention)/pallas_call"}
  %sort.9 = f32[4,16]{1,0} sort(%x), dimensions={1}, metadata={op_name="jit(step)/sample/jit(sort)/sort" stack_frame_id=190}
  %dot.4 = f32[4,16]{1,0} dot(%y, %z), metadata={op_name="jit(step)/lm_head/dot_general"}
  %copy.1 = f32[4]{0} copy(%w)
  ROOT %tuple = (f32[4]) tuple(%copy.1), metadata={op_name="jit(step)"}
}
"""


def test_op_scopes_from_hlo_text():
    got = spans.op_scopes(HLO)
    assert got == {"exponential.3": "sample", "gather.2": "embed",
                   "custom-call.7": "layers", "sort.9": "sample",
                   "dot.4": "lm_head"}
    assert spans.op_scopes("HloModule m\n") == {}


def test_op_scopes_find_the_sampler_in_a_compiled_engine_step():
    """The engine's own step, compiled on the CPU: every sort (top-p) is
    under ``sample``, and the model's parts carry their scopes."""
    from repro.configs.registry import ASSIGNED
    from repro.models.api import build_model
    from repro.runtime.engine import ServingEngine
    cfg = ASSIGNED["qwen3-0.6b"].reduced()
    model = build_model(cfg)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                        num_slots=2, max_seq=32, chunk_size=4, top_p=0.9,
                        block_size=8, num_blocks=8)
    text = eng.lower_step().compile().as_text()
    got = spans.op_scopes(text)
    sorts = [n for n in got if n.startswith("sort.")
             and f"%{n} = " in text and " sort(" in
             text.split(f"%{n} = ", 1)[1].split("\n", 1)[0]]
    assert sorts and all(got[n] == "sample" for n in sorts)
    assert set(got.values()) == set(spans.SCOPES)


def test_scope_seconds_average_over_devices():
    op_s = {"d0": {"sort.9": 0.3, "copy.1": 0.1, "dot.4": 0.2},
            "d1": {"sort.9": 0.1, "copy.1": 0.3}}
    got = spans.scope_s(op_s, {"sort.9": "sample", "dot.4": "lm_head"})
    assert got == pytest.approx({"sample": 0.2, "lm_head": 0.1, None: 0.2})


def test_idle_by_span_splits_idle_time_by_the_innermost_phase():
    dev = [Event(10, 20, "a"), Event(50, 60, "b")]
    host = [Event(0, 30, "serve.sync"), Event(30, 45, "serve.bookkeep"),
            Event(45, 55, "serve.schedule"),
            Event(0, 100, "serve_step"),          # not a phase: ignored
            Event(32, 40, "python frame")]
    got = spans.idle_by_span(dev, host, 0, 100)
    # idle: [0,10) [20,50) [60,100)
    assert got == {"serve.sync": 10 + 10, "serve.bookkeep": 15,
                   "serve.schedule": 5, None: 40}
    assert sum(got.values()) == 100 - trace.busy_ns(dev, 0, 100)
    # nested phases: the shorter one takes the instant
    host2 = [Event(0, 100, "serve.schedule"), Event(20, 30, "serve.wait")]
    assert spans.idle_by_span([], host2, 0, 100) == \
        {"serve.schedule": 90, "serve.wait": 10}


def test_idle_by_span_on_the_recorded_trace():
    """The recorded v5e trace predates the phases: all of its idle time
    lies under none, and a host annotation it does hold takes its
    share."""
    tr = trace.load(str(DATA))
    mark = tr.host_named("bench.traced_window")[0]
    dev = tr.devices[sorted(tr.devices)[0]]
    idle = mark.end - mark.start - trace.busy_ns(dev, mark.start, mark.end)
    assert spans.idle_by_span(dev, tr.host, mark.start, mark.end) == \
        {None: idle}
    got = spans.idle_by_span(dev, tr.host, mark.start, mark.end,
                             names=["host_work"])
    assert sum(got.values()) == idle and got.get("host_work", 0) > 0


def synthetic_run():
    """Four 0.11 s iterations in a one-second window, the third inside
    the traced stretch; a traced window with two devices."""
    phases = [sp(0.0, sched=0.001), sp(0.2, sched=0.002),
              sp(0.4, sched=0.030, disp=0.020), sp(0.6, sched=0.003)]
    reqs = [RequestRecord(rid=0, arrival_s=0.0, prompt_len=4,
                          max_new_tokens=2, greedy=True, n_out=2,
                          t_first_token=0.1, t_done=0.2)]
    tw = TracedWindow(t0_s=0.35, t1_s=0.55, window_s=0.2,
                      busy_s={"d0": 0.16, "d1": 0.14},
                      op_s={"d0": {"sort.9": 0.02, "fusion.1": 0.14},
                            "d1": {"sort.9": 0.01, "fusion.1": 0.13}},
                      gaps=[])
    run = RunData(seconds=1.0, setup_s=1.0, chips=2, dims={}, peaks={},
                  requests=reqs, steps=steps_of(phases), admissions=[],
                  preemptions=[], traced=tw)
    return run, phases


def test_the_five_readings_on_a_synthetic_run():
    run, phases = synthetic_run()
    assert spans.sched_ms(run, phases) == pytest.approx(2.0)
    assert spans.host_ms(run, phases) == pytest.approx(6.0)
    assert spans.dispatch_ms(run, phases) == pytest.approx(3.0)
    assert spans.sampling_pct(run, {"sort.9": "sample"}) == \
        pytest.approx(100 * 0.015 / 0.15)
    idle = {"serve.sync": 0.02, "serve.schedule": 0.004,
            "serve.bookkeep": 0.003, "serve.dispatch": 0.002, None: 0.001}
    assert spans.host_idle_pct(run, idle) == pytest.approx(100 * 0.009 / 0.2)
    # what a program without phases or scopes leaves: nothing to read
    assert spans.sched_ms(run, [{}] * 4) is None
    assert spans.sampling_pct(run, {}) is None
    assert spans.host_idle_pct(run, {None: 0.03}) is None
    run.traced = None
    assert spans.sampling_pct(run, {"sort.9": "sample"}) is None
    assert spans.host_idle_pct(run, idle) is None
    assert math.isclose(spans.sched_ms(run, phases), (1 + 2 + 30 + 3) / 4)


def test_engine_phases_reach_the_profiler_trace(tmp_path):
    """A traced CPU serve: the host plane holds each step's five phases
    by exact name, as many as the engine's step events, in the order the
    events give them."""
    from repro.configs.registry import ASSIGNED
    from repro.models.api import build_model
    from repro.runtime.engine import ServingEngine
    from repro.runtime.request import Request
    cfg = ASSIGNED["qwen3-0.6b"].reduced()
    model = build_model(cfg)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                        num_slots=2, max_seq=32, chunk_size=4,
                        telemetry=True)
    rng = np.random.RandomState(0)
    reqs = lambda: [Request(rid=i, tokens=rng.randint(0, cfg.vocab_size, 6),
                            max_new_tokens=3) for i in range(3)]
    eng.serve(reqs(), seed=0, realtime=False)          # compile first
    jax.profiler.start_trace(str(tmp_path))
    try:
        rep = eng.serve(reqs(), seed=0, realtime=False)
    finally:
        jax.profiler.stop_trace()
    tr = trace.load(str(sorted(tmp_path.rglob("*.xplane.pb"))[-1]))
    n = len(rep.timeline.events)
    for name in spans.PHASES[:5]:
        assert len(tr.host_named(name)) == n, name
    first = sorted((e for e in tr.host if e.name in spans.PHASES[:5]),
                   key=lambda e: e.start)[:5]
    assert [e.name for e in first] == list(spans.PHASES[:5])
    assert len(tr.host_named("serve_step")) >= n
