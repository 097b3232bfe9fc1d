"""Per-step serving telemetry: host phase spans, step timeline,
streaming percentiles and the Perfetto trace export.

Every claim about where a serving step's time goes ships with a per-step
evidence trail: one structured event per engine step, and the host
phases of the serving loop (``span``) recorded on that event and, as
``jax.profiler.TraceAnnotation``s, on the profiler's clock beside the
device's operations. Strictly host-side: telemetry never touches a
traced value, so ``step_compiles`` and the emitted tokens are identical
with it on or off (pinned in tests/test_telemetry.py and gated in
bench_serving.py).

Pieces:

* ``span`` — the one span mechanism: a context manager that annotates
  the profiler trace and, given a timeline, records ``(t0, t1)`` on the
  current step's event. The engine wraps each serve-loop iteration in a
  ``jax.profiler.StepTraceAnnotation`` and splits it into the disjoint
  phases of ``SERVE_PHASES`` (plus ``serve.wait`` when idle).
* ``LogHistogram`` — log-binned histogram on one geometric grid: a
  mergeable streaming percentile estimator with bounded relative error
  (one bin width) for every positive value. Used for TTFT, inter-token
  latency, queue wait, request latency and step wall-clock.
* ``StepEvent`` / ``StepTimeline`` — one structured event per engine
  step: phase mix per slot, occupancy, wall-clock, host phase spans,
  jit-compile events, preemptions, speculative and prefix-sharing
  counter deltas, and the *delta* of every TransferLedger (phase,
  category, direction) cell. Deltas are captured through the ledger's
  charge tap (see ``TransferLedger.attach_tap``), so the timeline's
  accumulated cells close bit-exactly against ``ledger.breakdown()`` —
  every charge path (admission growth, preemption, rollback, prefix
  hits, draft account) flows through the same tap.
* Exporters — a JSONL metrics sink (schema below, validated by
  ``validate_metrics_jsonl``) and a Chrome-trace/Perfetto JSON export
  (``write_chrome_trace``): steps and their host phases on the engine
  track, per-slot tracks, ledger byte counter tracks, droppable into
  https://ui.perfetto.dev.
* ``serve_report_lines`` — the ONE formatter behind serve.py's report
  and the ledger/spec/prefix/per-device summary, so the two report
  paths cannot drift.

JSONL event schema (one JSON object per line, ``"event"`` discriminates;
see docs/observability.md for the full field glossary):

  meta     run header: arch/quant/slots/chunk/dp/tp/spec/kv_quant
  admit    {rid, t, queue_wait_s}
  preempt  {rid, t}
  step     {step, t_start, t_end, wall_s, occupancy, compiles,
            counters, gauges, slots, ledger_delta, draft_delta, spans}
  summary  {steps, histograms, percentiles, ledger_total}
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax

from repro.runtime.transfers import D2H, H2D, TransferLedger

# Cell key: (phase, category, direction) — the ledger's grid flattened.
CellKey = Tuple[str, str, str]

#: The disjoint host phases of one serve-loop iteration that runs a
#: step, in order. ``serve.dispatch`` starts at the step's ``t_start``
#: and ``serve.sync`` ends at its ``t_end``.
SERVE_PHASES = ("serve.schedule", "serve.operands", "serve.dispatch",
                "serve.sync", "serve.bookkeep")

#: JSONL event types and the keys every instance must carry.
METRICS_SCHEMA: Dict[str, Tuple[str, ...]] = {
    "meta": ("version", "ts_unit"),
    "admit": ("rid", "t", "queue_wait_s"),
    "preempt": ("rid", "t"),
    "step": ("step", "t_start", "t_end", "wall_s", "occupancy",
             "compiles", "counters", "gauges", "slots", "ledger_delta",
             "spans"),
    "summary": ("steps", "histograms", "percentiles", "ledger_total"),
}


def _cell_str(key: CellKey) -> str:
    """``(phase, cat, dir)`` -> the JSONL's ``"phase/cat/dir"`` key."""
    return "/".join(key)


class Span:
    """One host phase as ``span`` measured it: ``[t0, t1)`` in
    ``time.perf_counter`` seconds (``t1`` is set on exit)."""

    __slots__ = ("t0", "t1")

    def __init__(self):
        self.t0 = self.t1 = 0.0


@contextlib.contextmanager
def span(name: str, timeline: Optional["StepTimeline"] = None
         ) -> Iterator[Span]:
    """Time one host phase of the serving loop.

    The phase enters ``jax.profiler.TraceAnnotation(name)``, so while a
    profiler trace runs it lands on the trace's clock in the host plane,
    beside the device's operations. With a ``timeline`` it is also
    recorded on the timeline's current step (``StepTimeline.add_span``).
    Yields the ``Span``, whose ``t0``/``t1`` are the phase's own stamps.
    """
    rec = Span()
    with jax.profiler.TraceAnnotation(name):
        rec.t0 = time.perf_counter()
        try:
            yield rec
        finally:
            rec.t1 = time.perf_counter()
    if timeline is not None:
        timeline.add_span(name, rec.t0, rec.t1)


class LogHistogram:
    """Log-binned histogram: a mergeable streaming quantile estimator.

    Every positive value lands in a bin of one geometric grid anchored
    at ``lo`` (``bins_per_decade`` bins per factor of 10, extending
    below ``lo`` and above ``hi`` as far as the values go; counts are
    kept sparse), so a percentile estimate is off by at most one bin
    width — a bounded *relative* error of ``10**(1/bins_per_decade) - 1``
    (~10% at the default 24/decade, halved in expectation by the
    geometric-midpoint readout) for every finite positive value,
    however many samples stream through. O(1) record, memory bounded by
    the decades the values span, mergeable across histograms with
    identical geometry (shard-local telemetry can be reduced without
    keeping raw samples). ``hi`` is the nominal top of the range: part
    of the geometry, not a bound.

    Zero and negative values (e.g. same-step inter-token gaps from
    accepted speculative lanes) fall into a dedicated zero bin. Exact
    ``min``, ``max``, ``sum`` and ``count`` ride along; percentile
    readouts are clamped to the observed [min, max], and the first and
    last ranks read the exact min and max.
    """

    def __init__(self, lo: float = 1e-6, hi: float = 1e5,
                 bins_per_decade: int = 24):
        if not (0 < lo < hi):
            raise ValueError(f"need 0 < lo < hi, got lo={lo} hi={hi}")
        self.lo, self.hi = float(lo), float(hi)
        self.bins_per_decade = int(bins_per_decade)
        self._zero = 0                      # values <= 0
        self._counts: Dict[int, int] = {}   # grid bin -> count
        self.count = 0
        self.sum = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None

    def _bin_of(self, v: float) -> int:
        """Grid bin of a positive value: ``[lo*g**b, lo*g**(b+1))`` with
        ``g = 10**(1/bins_per_decade)``; negative below ``lo``. Taken in
        log space, so no finite value overflows."""
        return math.floor((math.log10(v) - math.log10(self.lo))
                          * self.bins_per_decade)

    def record(self, v: float) -> None:
        """Stream one value in (O(1))."""
        v = float(v)
        if v > 0.0:
            b = self._bin_of(v)
            self._counts[b] = self._counts.get(b, 0) + 1
        else:
            self._zero += 1
        self.count += 1
        self.sum += v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)

    def merge(self, other: "LogHistogram") -> None:
        """Fold ``other``'s mass into this histogram (same geometry)."""
        if (other.lo, other.hi, other.bins_per_decade) != \
                (self.lo, self.hi, self.bins_per_decade):
            raise ValueError("cannot merge histograms with different "
                             "bin geometry")
        self._zero += other._zero
        for b, c in other._counts.items():
            self._counts[b] = self._counts.get(b, 0) + c
        self.count += other.count
        self.sum += other.sum
        for attr in ("min", "max"):
            o = getattr(other, attr)
            if o is None:
                continue
            s = getattr(self, attr)
            pick = min if attr == "min" else max
            setattr(self, attr, o if s is None else pick(s, o))

    @property
    def mean(self) -> float:
        """Exact arithmetic mean of the recorded values."""
        return self.sum / self.count if self.count else 0.0

    def percentile(self, q: float) -> float:
        """Nearest-rank ``q``-th percentile estimate.

        Walks the cumulative counts to the bin holding rank
        ``ceil(q/100 * count)`` and reads its geometric midpoint,
        clamped to the observed [min, max] — so the estimate is within
        one bin width (relative) of the exact nearest-rank quantile. The
        first and last ranks are the exact min and max.
        """
        if not self.count:
            return 0.0
        rank = max(1, int(math.ceil(q / 100.0 * self.count)))
        if rank >= self.count:
            return self.max
        if rank == 1:
            return self.min
        acc = self._zero
        if acc >= rank:
            return self.min                     # zero bin: all <= 0
        for b in sorted(self._counts):
            acc += self._counts[b]
            if acc >= rank:
                # Geometric midpoint of the bin, in log space and capped
                # at the max so that it cannot overflow.
                mid = math.log10(self.lo) + (b + 0.5) / self.bins_per_decade
                v = 10.0 ** min(mid, math.log10(self.max))
                return min(max(v, self.min), self.max)
        return self.max                           # pragma: no cover

    def percentiles(self, qs: Sequence[float] = (50, 90, 99)
                    ) -> Dict[str, float]:
        """{"p50": ..., ...} for each requested percentile."""
        return {f"p{g:g}": self.percentile(g) for g in qs}

    def to_dict(self) -> Dict:
        """JSON-ready state (sparse bins), invertible by ``from_dict``."""
        return {"lo": self.lo, "hi": self.hi,
                "bins_per_decade": self.bins_per_decade,
                "count": self.count, "sum": self.sum,
                "min": self.min, "max": self.max, "zero": self._zero,
                "bins": {str(b): self._counts[b]
                         for b in sorted(self._counts)}}

    @classmethod
    def from_dict(cls, d: Dict) -> "LogHistogram":
        """Rebuild a histogram from its ``to_dict`` form."""
        h = cls(d["lo"], d["hi"], d["bins_per_decade"])
        h.count, h.sum = int(d["count"]), float(d["sum"])
        h.min = d["min"] if d["min"] is None else float(d["min"])
        h.max = d["max"] if d["max"] is None else float(d["max"])
        h._zero = int(d["zero"])
        h._counts = {int(b): int(c) for b, c in d["bins"].items()}
        return h


@dataclasses.dataclass
class StepEvent:
    """One engine step, structured: timing, host phases, phase mix,
    counter deltas and the ledger-byte delta the step (plus any
    between-step admission / reservation charges since the previous
    event) moved."""

    step: int                       # 0-based step index
    t_start: float                  # serve-relative seconds: dispatch
    t_end: float                    # ... to the host's sync on the tokens
    occupancy: int                  # active slots during the step
    compiles: int                   # jit compilations this step (0 or 1)
    counters: Dict[str, float]      # per-step DELTAS of GenStats/sched
    gauges: Dict[str, float]        # point-in-time values (resident, ...)
    # Per-slot phase mix: (slot, rid, phase, fed_tokens, emitted_tokens)
    # where phase is "prefill" | "decode" | "verify" (speculating).
    slots: List[Tuple[int, int, str, int, int]]
    ledger_delta: Dict[CellKey, float]
    draft_delta: Optional[Dict[CellKey, float]] = None
    # Host phases of the serve-loop iteration that ran this step:
    # {name: [t0, t1]} in serve-relative seconds (see ``SERVE_PHASES``).
    spans: Dict[str, List[float]] = dataclasses.field(default_factory=dict)

    @property
    def wall_s(self) -> float:
        """Measured step wall time, dispatch to the host sync."""
        return self.t_end - self.t_start

    def to_json_dict(self) -> Dict:
        """The JSONL ``step`` event for this step."""
        d = {"event": "step", "step": self.step,
             "t_start": self.t_start, "t_end": self.t_end,
             "wall_s": self.wall_s, "occupancy": self.occupancy,
             "compiles": self.compiles, "counters": self.counters,
             "gauges": self.gauges,
             "slots": [list(s) for s in self.slots],
             "ledger_delta": {_cell_str(k): v
                              for k, v in self.ledger_delta.items()},
             "spans": {n: list(t) for n, t in self.spans.items()}}
        if self.draft_delta is not None:
            d["draft_delta"] = {_cell_str(k): v
                                for k, v in self.draft_delta.items()}
        return d


class StepTimeline:
    """Structured per-step event recorder for one serve() run.

    Attaches a charge *tap* to the run's ``TransferLedger`` (and the
    draft proposer's account, when present): every byte charged anywhere
    in the runtime — step chunks, shared weight streams, admission-time
    cache growth, preemption-path table uploads, rollback, prefix-hit
    accounting — is accumulated into the current step's delta AND a
    running total built from the identical sequence of additions, so
    ``ledger_delta_totals()`` equals ``ledger.breakdown()`` bit-exactly
    at any point (the closure guarantee; see docs/observability.md).

    The engine drives it with ``record_step`` after every unified step;
    the scheduler reports admissions/preemptions via ``on_admit`` /
    ``on_preempt``; token emission lands in ``on_token`` / ``on_done``.
    Host phases arrive through ``span``: ``open_iteration`` starts each
    serve-loop iteration, ``add_span`` records a phase on it, and the
    step event that ``record_step`` makes holds the iteration's phases —
    those that close after it (bookkeeping) too. An iteration that runs
    no step leaves its phases in the profiler trace only.
    Everything is plain host-side Python on small dicts — no device
    interaction, no traced values, no effect on jit caches.
    """

    #: Histogram metric names tracked by every timeline.
    HIST_NAMES = ("ttft_s", "itl_s", "queue_wait_s", "request_latency_s",
                  "step_wall_s")

    def __init__(self, ledger: TransferLedger, *,
                 draft_ledger: Optional[TransferLedger] = None,
                 meta: Optional[Dict] = None):
        self.ledger = ledger
        self.draft_ledger = draft_ledger
        self.meta = dict(meta or {})
        # perf_counter value of serve-relative time 0 (set by the engine).
        self.origin = 0.0
        self._spans: Dict[str, List[float]] = {}
        self.events: List[StepEvent] = []
        self.admissions: List[Tuple[int, float, float]] = []
        self.preemptions: List[Tuple[int, float]] = []
        self.hists: Dict[str, LogHistogram] = {
            n: LogHistogram() for n in self.HIST_NAMES}
        self._now = 0.0
        self._last_token_t: Dict[int, float] = {}
        self._prev_counters: Dict[str, float] = {}
        self._step_delta: Dict[CellKey, float] = {}
        self._cum: Dict[CellKey, float] = {}
        self._draft_step_delta: Dict[CellKey, float] = {}
        self._draft_cum: Dict[CellKey, float] = {}
        self._finalized = False
        ledger.attach_tap(self._tap)
        if draft_ledger is not None:
            draft_ledger.attach_tap(self._draft_tap)

    # -- ledger taps -----------------------------------------------------
    def _tap(self, phase: str, cat: str, direction: str,
             nbytes: float) -> None:
        k = (phase, cat, direction)
        self._step_delta[k] = self._step_delta.get(k, 0.0) + nbytes
        self._cum[k] = self._cum.get(k, 0.0) + nbytes

    def _draft_tap(self, phase: str, cat: str, direction: str,
                   nbytes: float) -> None:
        k = (phase, cat, direction)
        self._draft_step_delta[k] = \
            self._draft_step_delta.get(k, 0.0) + nbytes
        self._draft_cum[k] = self._draft_cum.get(k, 0.0) + nbytes

    # -- scheduler / engine hooks ----------------------------------------
    def open_iteration(self) -> None:
        """A serve-loop iteration begins: its phases go to a fresh map."""
        self._spans = {}

    def add_span(self, name: str, t0: float, t1: float) -> None:
        """Record host phase ``name`` over perf_counter ``[t0, t1)`` on
        the current iteration."""
        self._spans[name] = [t0 - self.origin, t1 - self.origin]

    def on_admit(self, rid: int, t: float, queue_wait_s: float) -> None:
        """One admission: record the queue-age sample and the event."""
        self.admissions.append((rid, t, queue_wait_s))
        self.hists["queue_wait_s"].record(queue_wait_s)
        self._now = max(self._now, t)

    def on_preempt(self, rid: int) -> None:
        """One preempt-to-queue event (stamped at the current stream
        time — preemption happens between steps)."""
        self.preemptions.append((rid, self._now))

    def on_token(self, rid: int, t: float,
                 ttft_s: Optional[float] = None) -> None:
        """One committed token: first tokens carry their TTFT; later
        ones record the inter-token gap (0 for extra tokens accepted
        within one speculative verify step — that is the point)."""
        if ttft_s is not None:
            self.hists["ttft_s"].record(ttft_s)
        else:
            last = self._last_token_t.get(rid)
            if last is not None:
                self.hists["itl_s"].record(t - last)
        self._last_token_t[rid] = t

    def on_done(self, rid: int, latency_s: float) -> None:
        """A request finished: record its end-to-end latency."""
        self.hists["request_latency_s"].record(latency_s)
        self._last_token_t.pop(rid, None)

    def record_step(self, *, t_start: float, t_end: float, occupancy: int,
                    compiles: int, counters: Dict[str, float],
                    gauges: Dict[str, float],
                    slots: List[Tuple[int, int, str, int, int]]) -> None:
        """Close out one engine step. ``counters`` are *cumulative*
        run-relative values (GenStats/scheduler tallies); the timeline
        diffs them against the previous step so every event carries
        per-step deltas that sum back to the run totals. The pending
        ledger tap deltas (charges since the previous event, including
        between-step admission/reservation charges) become the event's
        ``ledger_delta``."""
        delta = {k: counters[k] - self._prev_counters.get(k, 0)
                 for k in counters}
        self._prev_counters = dict(counters)
        led = self._step_delta
        self._step_delta = {}
        draft = None
        if self.draft_ledger is not None:
            draft = self._draft_step_delta
            self._draft_step_delta = {}
        ev = StepEvent(step=len(self.events), t_start=t_start,
                       t_end=t_end, occupancy=occupancy,
                       compiles=compiles, counters=delta, gauges=gauges,
                       slots=slots, ledger_delta=led, draft_delta=draft,
                       spans=self._spans)
        self.events.append(ev)
        self.hists["step_wall_s"].record(ev.wall_s)
        self._now = max(self._now, t_end)

    def finalize(self, t_end: float) -> None:
        """End of run: detach the ledger taps and fold any charges that
        landed after the last step (normally none — the serve loop only
        charges between a step and the next) into a zero-duration flush
        event, so the closure guarantee covers the whole run."""
        if self._finalized:
            return
        self._finalized = True
        self.ledger.detach_tap()
        if self.draft_ledger is not None:
            self.draft_ledger.detach_tap()
        if self._step_delta or self._draft_step_delta:
            self.open_iteration()
            self.record_step(t_start=t_end, t_end=t_end, occupancy=0,
                             compiles=0, counters=self._prev_counters,
                             gauges={}, slots=[])
        self._now = max(self._now, t_end)

    # -- views -----------------------------------------------------------
    def ledger_delta_totals(self) -> Dict[str, Dict[str, Dict[str, float]]]:
        """The timeline's accumulated cells, nested like
        ``TransferLedger.breakdown()``. Built from the identical
        per-charge addition sequence as the ledger's own cells, so it
        equals ``breakdown()`` bit-exactly — the closure invariant
        asserted in tests and in-bench."""
        out: Dict[str, Dict[str, Dict[str, float]]] = {}
        for (p, c, d), b in self._cum.items():
            out.setdefault(p, {}).setdefault(c, {})[d] = b
        return out

    def percentile_summary(self, qs: Sequence[float] = (50, 90, 99)
                           ) -> Dict[str, Dict[str, float]]:
        """{metric: {"p50": ..., ...}} over every tracked histogram."""
        return {n: h.percentiles(qs) for n, h in self.hists.items()}

    def phase_ms(self) -> Dict[str, float]:
        """Mean milliseconds per step of each host phase, over the step
        events that carry phases, in ``SERVE_PHASES`` order."""
        evs = [ev for ev in self.events if ev.spans]
        out: Dict[str, float] = {}
        for name in SERVE_PHASES:
            tot = sum(ev.spans[name][1] - ev.spans[name][0]
                      for ev in evs if name in ev.spans)
            if evs:
                out[name] = tot / len(evs) * 1e3
        return out

    # -- exporters -------------------------------------------------------
    def write_metrics_jsonl(self, path: str) -> None:
        """Write the JSONL metrics sink: meta header, admit/preempt and
        step events in time order, and a final summary line with the
        histograms, percentiles and the accumulated ledger totals."""
        with open(path, "w") as f:
            meta = {"event": "meta", "version": 1, "ts_unit": "s",
                    **self.meta}
            f.write(json.dumps(meta) + "\n")
            for rid, t, wait in self.admissions:
                f.write(json.dumps({"event": "admit", "rid": rid, "t": t,
                                    "queue_wait_s": wait}) + "\n")
            for rid, t in self.preemptions:
                f.write(json.dumps({"event": "preempt", "rid": rid,
                                    "t": t}) + "\n")
            for ev in self.events:
                f.write(json.dumps(ev.to_json_dict()) + "\n")
            summary = {
                "event": "summary", "steps": len(self.events),
                "histograms": {n: h.to_dict()
                               for n, h in self.hists.items()},
                "percentiles": self.percentile_summary(),
                "ledger_total": {
                    _cell_str(k): v for k, v in self._cum.items()},
            }
            f.write(json.dumps(summary) + "\n")

    def chrome_trace_events(self) -> List[Dict]:
        """The Chrome-trace ``traceEvents`` list: per-slot span tracks
        (phase-named complete events), an engine step track holding each
        step and its host phases (dispatch and sync nest inside the step,
        which they tile; schedule, operands and bookkeeping sit beside
        it), instant events for admissions/preemptions, and ledger-byte
        counter tracks (cumulative h2d/d2h MB per category), sorted by
        ts (a parent before the children it starts with)."""
        US = 1e6
        evs: List[Dict] = []
        pid = 1
        evs.append({"ph": "M", "name": "process_name", "pid": pid,
                    "tid": 0, "args": {"name": "serving-engine"}})
        evs.append({"ph": "M", "name": "thread_name", "pid": pid,
                    "tid": 0, "args": {"name": "engine steps"}})
        seen_slots = sorted({s[0] for ev in self.events
                             for s in ev.slots})
        for slot in seen_slots:
            evs.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": slot + 1,
                        "args": {"name": f"slot {slot}"}})
        cum_h2d: Dict[str, float] = {}
        cum_d2h: Dict[str, float] = {}
        for ev in self.events:
            ts, dur = ev.t_start * US, max(ev.wall_s * US, 1.0)
            evs.append({"ph": "X", "name": f"step {ev.step}",
                        "cat": "step", "ts": ts, "dur": dur,
                        "pid": pid, "tid": 0,
                        "args": {"occupancy": ev.occupancy,
                                 "compiles": ev.compiles}})
            for name, (a, b) in ev.spans.items():
                evs.append({"ph": "X", "name": name, "cat": "phase",
                            "ts": a * US, "dur": max((b - a) * US, 1e-3),
                            "pid": pid, "tid": 0, "args": {"step": ev.step}})
            for slot, rid, phase, fed, emitted in ev.slots:
                evs.append({"ph": "X", "name": phase, "cat": "slot",
                            "ts": ts, "dur": dur, "pid": pid,
                            "tid": slot + 1,
                            "args": {"rid": rid, "fed": fed,
                                     "emitted": emitted}})
            for (p, c, d), b in sorted(ev.ledger_delta.items()):
                tgt = cum_h2d if d == H2D else cum_d2h if d == D2H \
                    else None
                if tgt is not None:
                    tgt[c] = tgt.get(c, 0.0) + b
            te = ev.t_end * US
            evs.append({"ph": "C", "name": "ledger h2d MB", "pid": pid,
                        "tid": 0, "ts": te,
                        "args": {c: v / 1e6
                                 for c, v in sorted(cum_h2d.items())}})
            evs.append({"ph": "C", "name": "ledger d2h MB", "pid": pid,
                        "tid": 0, "ts": te,
                        "args": {c: v / 1e6
                                 for c, v in sorted(cum_d2h.items())}})
            if "resident_bytes" in ev.gauges:
                evs.append({"ph": "C", "name": "kv resident MB",
                            "pid": pid, "tid": 0, "ts": te,
                            "args": {"resident":
                                     ev.gauges["resident_bytes"] / 1e6}})
        for rid, t, wait in self.admissions:
            evs.append({"ph": "i", "name": f"admit rid={rid}", "s": "p",
                        "ts": t * US, "pid": pid, "tid": 0,
                        "args": {"queue_wait_ms": wait * 1e3}})
        for rid, t in self.preemptions:
            evs.append({"ph": "i", "name": f"preempt rid={rid}",
                        "s": "p", "ts": t * US, "pid": pid, "tid": 0,
                        "args": {}})
        evs.sort(key=lambda e: (e.get("ts", -1.0), e.get("ph") != "M",
                                -e.get("dur", 0.0)))
        return evs

    def write_chrome_trace(self, path: str) -> None:
        """Write the Perfetto-loadable Chrome trace JSON (open at
        https://ui.perfetto.dev or chrome://tracing)."""
        with open(path, "w") as f:
            json.dump({"traceEvents": self.chrome_trace_events(),
                       "displayTimeUnit": "ms",
                       "metadata": self.meta}, f)


# ---------------------------------------------------------------------------
# Validation (CI schema gate + tests)
# ---------------------------------------------------------------------------
def validate_metrics_jsonl(path: str) -> int:
    """Validate a JSONL metrics file against ``METRICS_SCHEMA``.

    Checks: every line parses as a JSON object with a known ``event``
    type carrying that type's required keys; exactly one leading meta
    and one trailing summary line; step indices dense from 0 with
    monotone non-decreasing ``t_start``; each step's ``spans`` a map of
    phase name to ``[t0, t1]`` with ``t0 <= t1``, the phases disjoint.
    Returns the number of step events; raises ``ValueError`` on any
    violation.
    """
    steps = 0
    last_t = -math.inf
    lines = []
    with open(path) as f:
        for i, raw in enumerate(f):
            try:
                obj = json.loads(raw)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{i + 1}: not JSON: {e}") from e
            if not isinstance(obj, dict) or "event" not in obj:
                raise ValueError(f"{path}:{i + 1}: missing 'event' key")
            kind = obj["event"]
            if kind not in METRICS_SCHEMA:
                raise ValueError(f"{path}:{i + 1}: unknown event "
                                 f"{kind!r}")
            missing = [k for k in METRICS_SCHEMA[kind] if k not in obj]
            if missing:
                raise ValueError(f"{path}:{i + 1}: {kind} event missing "
                                 f"keys {missing}")
            if kind == "step":
                if obj["step"] != steps:
                    raise ValueError(
                        f"{path}:{i + 1}: step index {obj['step']} != "
                        f"expected {steps} (must be dense from 0)")
                if obj["t_start"] < last_t:
                    raise ValueError(f"{path}:{i + 1}: t_start moved "
                                     "backwards")
                last_t = obj["t_start"]
                _check_spans(obj["spans"], f"{path}:{i + 1}")
                steps += 1
            lines.append(kind)
    if not lines or lines[0] != "meta":
        raise ValueError(f"{path}: first line must be the meta event")
    if lines[-1] != "summary":
        raise ValueError(f"{path}: last line must be the summary event")
    if lines.count("meta") != 1 or lines.count("summary") != 1:
        raise ValueError(f"{path}: exactly one meta and one summary "
                         "line required")
    return steps


def _check_spans(spans, where: str) -> None:
    """A step event's ``spans``: numeric ``[t0, t1]`` pairs, each in
    order, none overlapping another."""
    if not isinstance(spans, dict):
        raise ValueError(f"{where}: spans must be an object")
    ivs = []
    for name, iv in spans.items():
        if not (isinstance(iv, list) and len(iv) == 2 and all(
                isinstance(t, (int, float)) for t in iv) and iv[0] <= iv[1]):
            raise ValueError(f"{where}: span {name!r} is not [t0, t1] "
                             "with t0 <= t1")
        ivs.append(iv)
    ivs.sort()
    for a, b in zip(ivs, ivs[1:]):
        if b[0] < a[1]:
            raise ValueError(f"{where}: spans overlap")


def validate_chrome_trace(path: str) -> int:
    """Validate a Chrome-trace/Perfetto JSON export.

    Checks: the file parses, carries a ``traceEvents`` list, every span
    ("X") event has numeric ``ts``/``dur`` and a ``pid``/``tid``/
    ``name``, counter ("C") events carry numeric args, and ``ts`` is
    monotone non-decreasing across the sorted stream. Returns the span
    count; raises ``ValueError`` on violations.
    """
    with open(path) as f:
        data = json.load(f)
    evs = data.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        raise ValueError(f"{path}: no traceEvents list")
    spans = 0
    last_ts = -math.inf
    for i, e in enumerate(evs):
        ph = e.get("ph")
        if ph is None:
            raise ValueError(f"{path}: event {i} missing 'ph'")
        ts = e.get("ts")
        if ph != "M":
            if not isinstance(ts, (int, float)):
                raise ValueError(f"{path}: event {i} ({ph}) missing "
                                 "numeric 'ts'")
            if ts < last_ts:
                raise ValueError(f"{path}: event {i} ts moved backwards "
                                 f"({ts} < {last_ts})")
            last_ts = ts
        if ph == "X":
            spans += 1
            for k in ("dur", "pid", "tid", "name"):
                if k not in e:
                    raise ValueError(f"{path}: span event {i} missing "
                                     f"{k!r}")
            if not isinstance(e["dur"], (int, float)) or e["dur"] <= 0:
                raise ValueError(f"{path}: span event {i} has non-"
                                 "positive dur")
        if ph == "C":
            args = e.get("args")
            if not isinstance(args, dict) or not all(
                    isinstance(v, (int, float)) for v in args.values()):
                raise ValueError(f"{path}: counter event {i} needs "
                                 "numeric args")
    if spans == 0:
        raise ValueError(f"{path}: no span events")
    return spans


# ---------------------------------------------------------------------------
# The one serve-report formatter (serve.py + benches)
# ---------------------------------------------------------------------------
def serve_report_lines(engine, report,
                       total_requests: Optional[int] = None) -> List[str]:
    """Every report line for a finished serve run, from ONE place.

    Replaces serve.py's hand-rolled report and the ad-hoc per-device
    lines that used to overlap ``TransferReport.summary_lines`` —
    scheduler/occupancy, paged-arena, prefix-cache, speculative, timing,
    latency-percentile (telemetry histograms when available, finished-
    sequence lists otherwise), mesh, ledger and host-phase lines are all
    emitted here, so the CLI report and the summary cells cannot drift
    apart. ``total_requests`` defaults to the
    finished-sequence count (a serve run drains its stream)."""
    st = report.stats
    sched = report.sched
    total = total_requests if total_requests is not None \
        else len(report.sequences)
    lines = [
        f"completed {sched.completed}/{total} | "
        f"slot reuses {sched.slot_reuses} | "
        f"mean occupancy {sched.mean_occupancy:.2f}/{engine.num_slots} "
        f"(max {sched.max_occupancy}) | "
        f"step compiles {report.step_compiles}",
        f"chunk scheduling: {sched.prefill_chunks} prompt chunks | "
        f"{sched.deferred_feeds} budget-deferred feeds | "
        f"{st.prefill_tokens} prompt tokens streamed | mean queue wait "
        f"{sched.mean_queue_wait * 1e3:.1f} ms",
    ]
    if engine.paged:
        lines.append(
            f"paged arena: block reissues "
            f"{engine.arena.allocator.reissues} | preemptions "
            f"{sched.preemptions} | resident/token "
            f"{st.resident_bytes_per_token:.0f} B | peak resident "
            f"{st.peak_resident_bytes / 1e6:.2f} MB")
    if engine.prefix_cache:
        pc = engine.arena.prefix_cache
        lines.append(
            f"prefix cache: {st.prefix.hits}/{sched.admitted} "
            f"admissions hit | {st.prefix.hit_tokens} prompt tokens "
            f"from shared pages | {st.prefix.cow_splits} CoW splits | "
            f"{len(pc)} cached chains ({pc.evictions} evicted)")
    if engine.spec != "off":
        lines.append(
            f"speculative[{engine.spec} k={engine.spec_k}]: "
            f"accept {st.spec.accepted}/{st.spec.proposed} "
            f"({st.spec_accept_rate * 100:.0f}%) | rolled back "
            f"{st.spec.rolled_back} tok | steps/token "
            f"{st.steps_per_token:.3f} | weight-stream/token "
            f"{st.transfers.weight_stream_bytes_per_token / 1e6:.3f} MB"
            f" | lanes trimmed {sched.spec_lanes_trimmed}")
        if st.draft_transfers is not None:
            lines.append(
                f"draft account: "
                f"{st.draft_transfers.bytes_per_token / 1e6:.3f}"
                f" MB/proposal ({engine._proposer.steps} draft steps)")
    lines.append(
        f"prefill {st.prefill_s * 1e3:.1f} ms ({st.prefill_tokens} tok)"
        f" | decode {st.decode_s * 1e3:.1f} ms ({st.decode_tokens} tok, "
        f"{st.decode_tok_per_s:.1f} tok/s) | "
        f"throughput {report.throughput_tok_s:.1f} tok/s | "
        f"arena {st.cache_bytes / 1e6:.1f} MB")
    tl = report.timeline
    if tl is not None and tl.hists["request_latency_s"].count:
        pct = tl.hists["request_latency_s"].percentiles((50, 90, 99))
        tp = tl.hists["ttft_s"].percentiles((50, 99))
        lines.append(
            f"latency p50 {pct['p50'] * 1e3:.0f} ms | p90 "
            f"{pct['p90'] * 1e3:.0f} ms | p99 {pct['p99'] * 1e3:.0f} ms"
            f" | ttft p50 {tp['p50'] * 1e3:.0f} ms p99 "
            f"{tp['p99'] * 1e3:.0f} ms (streaming estimators)")
    else:
        pct = report.latency_percentiles((50, 90, 99))
        lines.append(
            f"latency p50 {pct[50] * 1e3:.0f} ms | p90 "
            f"{pct[90] * 1e3:.0f} ms | p99 {pct[99] * 1e3:.0f} ms")
    if engine.mesh is not None:
        tr = st.transfers
        line = (f"mesh dp={engine.dp} tp={engine.tp}: per-device "
                f"bytes/token {tr.per_device_bytes_per_token / 1e6:.3f} "
                f"MB | per-device weight-stream/token "
                f"{tr.per_device_weight_stream_bytes_per_token / 1e6:.3f}"
                f" MB")
        if engine.paged:
            line += (f" | per-device paged-read/token "
                     f"{(st.paged.read_bytes_per_device / max(st.decode_tokens, 1)) / 1e6:.3f} MB")
        lines.append(line)
    lines.append("transfer ledger (host<->device):")
    exec_s = {"prefill": st.prefill_s, "decode": st.decode_s}
    lines.extend(f"  {ln}"
                 for ln in report.ledger.summary_lines(exec_s))
    phases = tl.phase_ms() if tl is not None else {}
    if phases:
        lines.append("host phases (mean ms/step): " + " | ".join(
            f"{n.split('.', 1)[1]} {v:.2f}" for n, v in phases.items()))
    return lines


def _main(argv: Optional[List[str]] = None) -> int:
    """CLI: ``python -m repro.runtime.telemetry validate FILE...`` —
    schema-validate ``.jsonl`` metrics and ``.json`` trace exports
    (used by the CI artifact-validation step)."""
    import argparse
    ap = argparse.ArgumentParser(prog="repro.runtime.telemetry")
    ap.add_argument("command", choices=["validate"])
    ap.add_argument("files", nargs="+")
    args = ap.parse_args(argv)
    for path in args.files:
        if path.endswith(".jsonl"):
            n = validate_metrics_jsonl(path)
            print(f"{path}: valid metrics JSONL ({n} step events)")
        else:
            n = validate_chrome_trace(path)
            print(f"{path}: valid Perfetto/Chrome trace ({n} spans)")
    return 0


if __name__ == "__main__":                         # pragma: no cover
    raise SystemExit(_main())
