"""Host phases and device scopes of the serving step, reduced.

The program marks the host phases of each serve-loop iteration as spans
(``serve.schedule``, ``serve.operands``, ``serve.dispatch``,
``serve.sync``, ``serve.bookkeep``; ``serve.wait`` when idle): on the
engine's step events as ``{name: [t0, t1]}`` in window seconds, and in
a profiler trace as host annotations of the same names. It names the
parts of the jitted step as scopes (``embed``, ``layers``, ``lm_head``,
``sample``) in every op's metadata. The functions below reduce both:

- ``phase_ms``: mean milliseconds per step of some phases, over the
  window's steps outside the traced stretch;
- ``op_scopes`` / ``scope_s``: the compiled step's HLO text maps each
  device op's instruction name (what a trace prints, e.g. ``sort.9``) to
  its scope, and the trace's per-op time sums by scope;
- ``idle_by_span``: the device's idle time within a trace window, split
  by the innermost host phase that covers it.

The last five functions are the per-layer readings built on them, each
from a run's ``RunData`` and what it does not hold: the step events'
spans (parallel to ``RunData.steps``), the op-to-scope map, and the
traced window's idle seconds per phase (``idle_by_span`` in seconds).
Spans are read by exact name. A program without spans or scopes gives
nothing to read (empty maps, None), never an error.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench.trace import Event, idle_gaps

SCOPES = ("embed", "layers", "lm_head", "sample")
PHASES = ("serve.schedule", "serve.operands", "serve.dispatch",
          "serve.sync", "serve.bookkeep", "serve.wait")
# Host work the device waits through: every phase but the wait for the
# step's tokens and the idle wait for arrivals.
HOST_PHASES = ("serve.schedule", "serve.operands", "serve.dispatch",
               "serve.bookkeep")

Spans = Dict[str, List[float]]

# One HLO instruction with op_name metadata: its name and the op_name.
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+) = .*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"', re.M)


def phase_ms(steps: Sequence[Tuple[float, float, list]],
             spans: Sequence[Spans], seconds: float,
             names: Iterable[str],
             traced: Optional[Tuple[float, float]] = None
             ) -> Optional[float]:
    """Mean milliseconds per step spent in the phases ``names``, over
    the steps that ended in ``[0, seconds)`` and carry spans. A step
    whose phases touch the traced stretch ``traced`` (window seconds) is
    left out, so the profiler's own host cost does not enter. ``spans``
    runs parallel to ``steps``. None when no step is left."""
    names = tuple(names)
    tot, n = 0.0, 0
    for (_, t_end, _), sp in zip(steps, spans):
        if not sp or not 0.0 <= t_end < seconds:
            continue
        if traced is not None and any(a < traced[1] and b > traced[0]
                                      for a, b in sp.values()):
            continue
        tot += sum(sp[k][1] - sp[k][0] for k in names if k in sp)
        n += 1
    return tot / n * 1e3 if n else None


def op_scopes(hlo_text: str, scopes: Sequence[str] = SCOPES
              ) -> Dict[str, str]:
    """Instruction name -> the outermost of ``scopes`` in its
    ``op_name`` metadata, over every computation of a compiled module's
    text (fusion bodies and loop bodies included). Instructions under
    none of the scopes are left out."""
    want = set(scopes)
    out: Dict[str, str] = {}
    for name, op_name in _INSTR.findall(hlo_text):
        hit = next((p for p in op_name.split("/") if p in want), None)
        if hit is not None:
            out[name] = hit
    return out


def scope_s(op_s: Dict[str, Dict[str, float]], scopes: Dict[str, str]
            ) -> Dict[Optional[str], float]:
    """Device seconds per scope (None: ops under no scope), averaged
    over the device planes of ``op_s`` (plane -> op name -> seconds)."""
    out: Dict[Optional[str], float] = {}
    for ops in op_s.values():
        for op, s in ops.items():
            k = scopes.get(op)
            out[k] = out.get(k, 0.0) + s / len(op_s)
    return out


def idle_by_span(device: Sequence[Event], host: Sequence[Event], w0: int,
                 w1: int, names: Iterable[str] = PHASES
                 ) -> Dict[Optional[str], int]:
    """Nanoseconds of ``[w0, w1)`` in which ``device`` ran nothing, split
    by the innermost (shortest) host event named in ``names`` that
    covers each instant; None collects idle time under none of them."""
    names = set(names)
    marks = sorted((e for e in host if e.name in names),
                   key=lambda e: e.start)
    out: Dict[Optional[str], int] = {}
    for a, b in sorted(idle_gaps(device, w0, w1)):
        cover = [e for e in marks if e.start < b and e.end > a]
        cuts = sorted({a, b} | {t for e in cover for t in (e.start, e.end)
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            inner = min((e for e in cover if e.start <= x and e.end >= y),
                        key=lambda e: e.end - e.start, default=None)
            k = inner.name if inner is not None else None
            out[k] = out.get(k, 0) + (y - x)
    return out


def sched_ms(run, spans: Sequence[Spans]) -> Optional[float]:
    """Scheduler: mean ``serve.schedule`` per step (admission, block
    reservation, feed planning)."""
    return _window_phase_ms(run, spans, ("serve.schedule",))


def host_ms(run, spans: Sequence[Spans]) -> Optional[float]:
    """Engine host loop: mean ``serve.operands`` plus ``serve.bookkeep``
    per step."""
    return _window_phase_ms(run, spans, ("serve.operands",
                                         "serve.bookkeep"))


def dispatch_ms(run, spans: Sequence[Spans]) -> Optional[float]:
    """Engine step enqueue: mean ``serve.dispatch`` per step."""
    return _window_phase_ms(run, spans, ("serve.dispatch",))


def sampling_pct(run, scopes: Dict[str, str]) -> Optional[float]:
    """Sampling: device time of the ops under the ``sample`` scope over
    the busy time of the traced window, in percent."""
    tw = run.traced
    if tw is None:
        return None
    s = scope_s(tw.op_s, scopes).get("sample", 0.0)
    return 100.0 * s / tw.mean_busy_s if s > 0 else None


def host_idle_pct(run, idle_s: Dict[Optional[str], float]
                  ) -> Optional[float]:
    """Engine host loop: device idle time of the traced window under
    ``HOST_PHASES``, over the window, in percent."""
    tw = run.traced
    if tw is None or not any(k in idle_s for k in PHASES):
        return None
    return 100.0 * sum(idle_s.get(k, 0.0) for k in HOST_PHASES) \
        / tw.window_s


def _window_phase_ms(run, spans, names) -> Optional[float]:
    tw = run.traced
    return phase_ms(run.steps, spans, run.seconds, names,
                    None if tw is None else (tw.t0_s, tw.t1_s))
