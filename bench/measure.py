"""What one run measured, and the arithmetic every metric reader shares.

``RunData`` holds the raw record of a run — each request's host stamps,
the engine's step events, admissions and preemptions, the traced window
reduced to device time — and derives the quantities the readers under
``bench/metrics/`` report. All times are seconds from the start of the
measured window unless a name says otherwise; percentiles are exact
over raw values.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import counters

# Substring that names the fused paged-attention kernel's ops in a
# device trace.
KERNEL_OP = "paged_decode_attention"


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile of ``values``, interpolated linearly between
    order statistics (numpy's default); ``inf`` entries stand for requests
    that missed every limit, and a percentile that reaches one is ``inf``.
    None when there are no values."""
    if not len(values):
        return None
    v = np.sort(np.asarray(values, np.float64))
    pos = q / 100.0 * (len(v) - 1)
    lo, frac = int(math.floor(pos)), pos - math.floor(pos)
    if frac == 0.0:
        return float(v[lo])
    if not np.isfinite(v[lo + 1]):
        return math.inf
    return float(v[lo] + frac * (v[lo + 1] - v[lo]))


@dataclasses.dataclass
class RequestRecord:
    """One request due in the window, with what the engine stamped."""

    rid: int
    arrival_s: float
    prompt_len: int
    max_new_tokens: int
    greedy: bool
    n_out: int = 0
    t_first_token: Optional[float] = None
    t_done: Optional[float] = None

    @property
    def completed(self) -> bool:
        """Finished with every token it asked for."""
        return self.t_done is not None and self.n_out == self.max_new_tokens


@dataclasses.dataclass
class SlotWork:
    """One sequence's share of one engine step, rebuilt by ``replay``."""

    step: int
    rid: int
    ctx: int            # tokens cached before the step
    fed: int            # tokens fed this step
    sampled: int        # tokens sampled (emitted) this step


@dataclasses.dataclass
class TracedWindow:
    """The traced part of the window, reduced (see ``bench.trace``)."""

    t0_s: float                 # window-relative start and end (host)
    t1_s: float
    window_s: float             # length on the trace's clock
    busy_s: Dict[str, float]            # per device plane
    op_s: Dict[str, Dict[str, float]]   # per device plane, per op name
    gaps: List[Tuple[str, float]]       # longest idle gaps, labelled

    @property
    def mean_busy_s(self) -> float:
        """Busy seconds, averaged over the devices in the trace."""
        return sum(self.busy_s.values()) / len(self.busy_s)

    def mean_op_s(self, name_has: str) -> float:
        """Device seconds of ops whose name contains ``name_has``,
        averaged over the devices."""
        tot = sum(s for ops in self.op_s.values()
                  for n, s in ops.items() if name_has in n)
        return tot / len(self.op_s)

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """The ``k`` op names with the most device time (device mean)."""
        agg: Dict[str, float] = {}
        for ops in self.op_s.values():
            for n, s in ops.items():
                agg[n] = agg.get(n, 0.0) + s / len(self.op_s)
        return sorted(agg.items(), key=lambda kv: -kv[1])[:k]


@dataclasses.dataclass
class RunData:
    """Everything a metric reader may read about one run."""

    seconds: float                      # length of the measured window
    setup_s: float
    chips: int
    dims: Dict[str, int]                # model sizes (see counters)
    peaks: Dict[str, float]
    requests: List[RequestRecord]
    # Engine step events: (t_start, t_end, [(slot, rid, phase, fed,
    # emitted), ...]) in step order; window-relative times.
    steps: List[Tuple[float, float, list]]
    admissions: List[Tuple[int, float]]     # (rid, t) in order
    preemptions: List[Tuple[int, float]]    # (rid, t) in order
    traced: Optional[TracedWindow] = None

    # -- request tails ---------------------------------------------------
    def ttft_ms(self) -> List[float]:
        """Due time to first token, per request due in the window; a
        request that did not complete counts as infinitely late."""
        return [(r.t_first_token - r.arrival_s) * 1e3
                if r.completed else math.inf for r in self.requests]

    def emissions(self) -> Dict[int, List[float]]:
        """Per request, the times its kept output tokens were emitted:
        the end of the step that sampled each (the engine stamps tokens
        after the step's host sync). Tokens emitted before a preemption
        are recomputed later, so a preemption starts the list anew."""
        out: Dict[int, List[float]] = {}
        pre = sorted(self.preemptions, key=lambda p: p[1])
        i = 0
        for t0, t1, slots in self.steps:
            while i < len(pre) and pre[i][1] <= t0:
                out.pop(pre[i][0], None)
                i += 1
            for _, rid, _, _, emitted in slots:
                if emitted:
                    out.setdefault(rid, []).extend([t1] * emitted)
        return out

    def itl_ms(self) -> List[float]:
        """Every gap between consecutive output tokens of every request
        due in the window, wherever the gap falls; a request that did not
        complete contributes all of its gaps as infinitely late."""
        em = self.emissions()
        gaps: List[float] = []
        for r in self.requests:
            t = em.get(r.rid, [])
            if not r.completed or len(t) != r.n_out:
                gaps += [math.inf] * max(r.max_new_tokens - 1, 1)
            else:
                gaps += [(b - a) * 1e3 for a, b in zip(t, t[1:])]
        return gaps

    def queue_wait_ms(self) -> List[float]:
        """Due time to first admission, per request due in the window."""
        first: Dict[int, float] = {}
        for rid, t in self.admissions:
            first.setdefault(rid, t)
        return [(first[r.rid] - r.arrival_s) * 1e3 if r.rid in first
                else math.inf for r in self.requests]

    # -- steps -----------------------------------------------------------
    def window_steps(self) -> List[Tuple[float, float, list]]:
        """Steps that ended inside the window."""
        return [s for s in self.steps if 0.0 <= s[1] < self.seconds]

    def mean_step_ms(self) -> Optional[float]:
        """Mean host span of an engine step (dispatch to the host's sync
        on its result), over the window's steps."""
        steps = self.window_steps()
        if not steps:
            return None
        return sum(t1 - t0 for t0, t1, _ in steps) / len(steps) * 1e3

    def window_preemptions(self) -> int:
        """Preempt-to-queue events inside the window."""
        return sum(1 for _, t in self.preemptions if 0.0 <= t < self.seconds)

    def window_tokens(self) -> int:
        """Output tokens emitted by steps that ended inside the window and
        kept (see ``emissions``)."""
        return sum(1 for ts in self.emissions().values() for t in ts
                   if 0.0 <= t < self.seconds)

    def stalls(self, min_ms: float = 500.0) -> List[Tuple[float, float, float]]:
        """Intervals between consecutive step ends inside the window longer
        than ``min_ms``: (previous end, end, the step's own span in ms),
        longest first."""
        steps = self.window_steps()
        out = [(a[1], b[1], (b[1] - b[0]) * 1e3)
               for a, b in zip(steps, steps[1:])
               if (b[1] - a[1]) * 1e3 > min_ms]
        return sorted(out, key=lambda s: s[0] - s[1])

    def step_summary(self) -> Dict[str, object]:
        """How the window's steps were spaced, for the run's log: the
        interval from one step's end to the next, its quantiles, the
        intervals longer than 1.5 times the median (stalls), and the
        longest over half a second with the span of the step that ended
        them (the rest of such an interval is host time between steps)."""
        ends = [t1 for _, t1, _ in self.window_steps()]
        if len(ends) < 3:
            return {"steps": len(ends)}
        iv = np.diff(ends) * 1e3
        med = float(np.median(iv))
        slow = iv[iv > 1.5 * med]
        return {"steps": len(ends), "interval_p50_ms": med,
                "interval_p95_ms": float(np.percentile(iv, 95)),
                "interval_max_ms": float(iv.max()),
                "stalls": int(len(slow)),
                "stall_excess_s": float((slow - med).sum() / 1e3),
                "longest": [[round(a, 3), round((b - a) * 1e3, 1),
                             round(span, 1)]
                            for a, b, span in self.stalls()[:3]]}

    def replay(self) -> List[SlotWork]:
        """Each step's per-sequence work, with the cached context rebuilt
        from the step events alone: a sequence's context grows by what it
        is fed and restarts from nothing when it is preempted."""
        ctx: Dict[int, int] = {}
        pre = sorted(self.preemptions, key=lambda p: p[1])
        i, out = 0, []
        for k, (t0, _, slots) in enumerate(self.steps):
            while i < len(pre) and pre[i][1] <= t0:
                ctx[pre[i][0]] = 0
                i += 1
            for _, rid, _, fed, emitted in slots:
                if fed:
                    c = ctx.get(rid, 0)
                    out.append(SlotWork(k, rid, c, fed, emitted))
                    ctx[rid] = c + fed
        return out

    def _traced_work(self):
        """Replayed work of the steps whose middle lies in the traced
        window."""
        tw = self.traced
        mids = {k for k, (t0, t1, _) in enumerate(self.steps)
                if tw.t0_s <= (t0 + t1) / 2 < tw.t1_s}
        return [w for w in self.replay() if w.step in mids]

    # -- device ----------------------------------------------------------
    def mfu_pct(self) -> Optional[float]:
        """Useful model FLOPs of the traced window's steps over the
        window's length times the chips' bf16 peak, in percent."""
        if self.traced is None:
            return None
        flops = sum(counters.model_flops(self.dims, w.ctx, w.fed, w.sampled)
                    for w in self._traced_work())
        if not flops:
            return None
        return 100.0 * flops / (self.traced.window_s * self.chips
                                * self.peaks["bf16_flops_per_s"])

    def kernel_roofline_pct(self, name_has: str) -> Optional[float]:
        """Roofline time of the paged attention the traced steps needed
        (per chip: tensor parallelism splits the heads) over the device
        time of the kernel's ops, in percent. None when the trace holds
        no such op."""
        if self.traced is None:
            return None
        kernel_s = self.traced.mean_op_s(name_has)
        if kernel_s <= 0:
            return None
        # One kernel call serves every sequence of a step, so the bound is
        # taken per step over the step's summed work.
        per_step: Dict[int, List[int]] = {}
        for w in self._traced_work():
            f, b = counters.paged_attention_work(self.dims, w.ctx, w.fed)
            acc = per_step.setdefault(w.step, [0, 0])
            acc[0] += f
            acc[1] += b
        need = sum(counters.roofline_seconds(
            f / self.chips, b / self.chips, self.peaks["bf16_flops_per_s"],
            self.peaks["hbm_bytes_per_s"]) for f, b in per_step.values())
        return 100.0 * need / kernel_s if need else None

    def idle_pct(self) -> Optional[float]:
        """Share of the traced window in which the device ran nothing,
        averaged over the chips, in percent."""
        if self.traced is None:
            return None
        return 100.0 * (1.0 - self.traced.mean_busy_s / self.traced.window_s)
