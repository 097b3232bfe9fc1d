"""One run of one cell: set up, measure a window, check, report.

The order is fixed by what each step may disturb:

1. set-up (``setup_s``): the compile cache at its fixed path, weights
   made on the device from the seed in one jitted call, the serving
   engine, and a short warm-up serve that compiles every program the
   window runs;
2. the window: ``ServingEngine.serve(requests, realtime=True)`` over the
   cell's open-loop requests, due over ``[0, seconds)``; ``serve`` drains
   what was due, and only what happened inside the window is counted.
   With ``trace`` a profiler trace covers a steady stretch of it;
3. the readings: metrics from the engine's own stamps and step events
   and from the trace, then the peak device memory;
4. the check (``correct``): the program's state is freed and the plain
   float32 reference reruns a sample of the greedy requests, prompt and
   served tokens, comparing logits (``bench.check``).
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import check as check_mod
from bench import measure, spec, traffic
from bench import trace as trace_mod

# Fraction of the window before the traced stretch starts (the arena
# fills and the queue reaches its level first), and the stretch's
# longest length: a few hundred steps keep the trace small enough to
# read within the run's time limit.
TRACE_START, TRACE_MAX_S = 0.4, 6.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def device_check(chips: int):
    """The devices a cell runs on; raises NoChip unless they are TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's platform is {devs[0].platform!r} "
                     f"({len(devs)} device(s)); this benchmark never "
                     f"falls back to it")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (fixed path inside the
    checkout, or ``JAX_COMPILATION_CACHE_DIR``), holding every program."""
    import jax
    from repro.launch.serve import use_compile_cache
    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts executables built (compiled or loaded from the persistent
    cache) while ``active``."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.active, self.count = False, 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event == self._event:
            self.count += 1


def build_program(cell: spec.Cell, seed: int):
    """The serving engine over the cell's model, with weights made from
    ``seed`` in one jitted call (sharded over the cell's mesh as they are
    made)."""
    import jax
    from repro.configs.base import ModelConfig
    from repro.launch.mesh import make_mesh
    from repro.models.api import build_model
    from repro.parallel.sharding import serving_param_shardings
    from repro.runtime.engine import ServingEngine

    cfg, eng = cell.config, cell.config["engine"]
    arch = cell.arch()
    model = build_model(ModelConfig(name=cell.config["name"],
                                    **arch.program_config_fields(cfg)))
    mesh = make_mesh(tuple(eng["mesh"]), ("data", "model")) \
        if eng.get("mesh") else None
    key = jax.random.key(seed)
    make = lambda k: arch.to_program(k, cfg)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(make, key)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the benchmark's weights do not fit the program's "
                         "parameter tree")
    shardings = serving_param_shardings(want, mesh) if mesh else None
    params = jax.jit(make, out_shardings=shardings)(key)
    jax.block_until_ready(params)
    return ServingEngine(
        model, params, num_slots=eng["num_slots"], max_seq=eng["max_seq"],
        chunk_size=eng["chunk_size"], block_size=eng["block_size"],
        num_blocks=eng["num_blocks"], paged_attn="fused", mesh=mesh,
        telemetry=True)


def to_requests(specs: List[traffic.RequestSpec]):
    """The program's request objects for the generated traffic."""
    from repro.runtime.request import Request, SamplingParams
    return [Request(rid=s.rid, tokens=s.prompt,
                    max_new_tokens=s.max_new_tokens,
                    sampling=SamplingParams(temperature=s.temperature,
                                            top_p=s.top_p),
                    arrival_s=s.arrival_s) for s in specs]


def warm_up(engine, cell: spec.Cell, seed: int) -> None:
    """Serve a few short requests through the same engine so every
    program the window runs is built before it starts: the step, the
    sampler's key split, table uploads, slot turnover. Prompts span more
    than one chunk, and both sampling modes run."""
    chunk = cell.config["engine"]["chunk_size"]
    rng = np.random.default_rng(seed)
    vocab = cell.config["vocab_size"]
    specs = [traffic.RequestSpec(i, 0.0, rng.integers(0, vocab, n,
                                                      dtype=np.int32),
                                 4, t, p)
             for i, (n, t, p) in enumerate([(2 * chunk + 3, 0.0, 1.0),
                                            (chunk - 1, 0.7, 0.95)])]
    engine.serve(to_requests(specs), seed=seed % 2 ** 31, realtime=False)


class Tracer(threading.Thread):
    """Profiles ``[t0 + start, t0 + stop)`` of the host clock into
    ``outdir`` while the main thread serves. The stretch is marked by a
    host annotation on this thread, whose ends give the traced window on
    the trace's own clock."""

    MARK = "bench.traced_window"

    def __init__(self, outdir: str, t0: float, start: float, stop: float):
        super().__init__(daemon=True)
        self.outdir, self.t0, self.start_s, self.stop_s = outdir, t0, \
            start, stop
        self.span = None            # window-relative (start, end) traced
        self.error: Optional[BaseException] = None

    def run(self):
        import jax
        try:
            time.sleep(max(self.t0 + self.start_s - time.perf_counter(), 0))
            jax.profiler.start_trace(self.outdir)
            try:
                with jax.profiler.TraceAnnotation(self.MARK):
                    a = time.perf_counter()
                    time.sleep(max(self.t0 + self.stop_s - a, 0))
                    b = time.perf_counter()
            finally:
                jax.profiler.stop_trace()
            self.span = (a - self.t0, b - self.t0)
        except BaseException as e:          # reported by the main thread
            self.error = e


class StackSampler(threading.Thread):
    """Samples the serving thread's innermost frames every ``period``
    seconds while the window runs, so that a stall in the run's log names
    the code the serving loop was in (the benchmark's own diagnosis; no
    metric reads it)."""

    def __init__(self, t0: float, period: float = 0.1, depth: int = 6):
        super().__init__(daemon=True)
        self.tid, self.t0 = threading.get_ident(), t0
        self.period, self.depth = period, depth
        self.samples: List[tuple] = []      # (window time, frames)
        self.done = threading.Event()

    def run(self):
        while not self.done.wait(self.period):
            frame = sys._current_frames().get(self.tid)
            where = []
            while frame is not None and len(where) < self.depth:
                code = frame.f_code
                where.append(f"{Path(code.co_filename).name}:"
                             f"{frame.f_lineno}:{code.co_name}")
                frame = frame.f_back
            self.samples.append((time.perf_counter() - self.t0,
                                 " < ".join(where)))

    def within(self, a: float, b: float, k: int = 3) -> List[list]:
        """The ``k`` most sampled frames in window time ``[a, b]``."""
        hits: Dict[str, int] = {}
        for t, where in self.samples:
            if a <= t <= b:
                hits[where] = hits.get(where, 0) + 1
        return [[n, w] for w, n in sorted(hits.items(),
                                          key=lambda kv: -kv[1])[:k]]


def reduce_trace(outdir: str, span) -> measure.TracedWindow:
    """Busy time, per-op time and labelled idle gaps of the traced
    window, from the profile written under ``outdir``."""
    paths = sorted(Path(outdir).rglob("*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {outdir}")
    tr = trace_mod.load(str(paths[-1]))
    marks = tr.host_named(Tracer.MARK)
    if not marks or not tr.devices:
        raise RuntimeError("the trace holds no window mark or no TPU plane")
    w0, w1 = marks[0].start, marks[0].end
    busy, ops = {}, {}
    for dev, evs in tr.devices.items():
        busy[dev] = trace_mod.busy_ns(evs, w0, w1) / 1e9
        ops[dev] = {n: t / 1e9 for n, t in
                    trace_mod.per_op_ns(evs, w0, w1).items()}
    first = sorted(tr.devices)[0]
    main_host = [e for e in tr.host if e.name != Tracer.MARK]
    gaps = [(trace_mod.label_at(main_host, (a + b) // 2), (b - a) / 1e9)
            for a, b in trace_mod.idle_gaps(tr.devices[first], w0, w1)[:10]]
    return measure.TracedWindow(t0_s=span[0], t1_s=span[1],
                                window_s=(w1 - w0) / 1e9, busy_s=busy,
                                op_s=ops, gaps=gaps)


def run_data(cell: spec.Cell, specs, report, seconds: float,
             setup_s: float, traced, dims: Dict[str, int],
             peaks: Dict[str, float]) -> measure.RunData:
    """The run's raw record, restricted to requests due in the window."""
    seqs = {s.rid: s for s in report.sequences}
    recs = []
    for sp in specs:
        s = seqs.get(sp.rid)
        recs.append(measure.RequestRecord(
            rid=sp.rid, arrival_s=sp.arrival_s,
            prompt_len=len(sp.prompt), max_new_tokens=sp.max_new_tokens,
            greedy=sp.greedy,
            n_out=len(s.generated) if s is not None else 0,
            t_first_token=s.t_first_token if s is not None else None,
            t_done=s.t_done if s is not None else None))
    tl = report.timeline
    return measure.RunData(
        seconds=seconds, setup_s=setup_s, chips=cell.chips, dims=dims,
        peaks=peaks, requests=recs,
        steps=[(e.t_start, e.t_end, list(e.slots)) for e in tl.events],
        admissions=[(rid, t) for rid, t, _ in tl.admissions],
        preemptions=list(tl.preemptions), traced=traced)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


@dataclasses.dataclass
class Window:
    """What one measured window leaves for the readers and the check, the
    program's state already freed."""

    data: measure.RunData
    sample: list            # finished greedy (prompt, served tokens)
    window_compiles: int
    devices: list
    memory_peak_bytes: int


def serve_window(cell: spec.Cell, seed: int, seconds: float, trace: bool,
                 t_process: float, chip_check: bool = True,
                 rate: Optional[float] = None) -> Window:
    """Set up, serve the cell's traffic due over ``[0, seconds)`` (at the
    cell's rate, or ``rate``), reduce the trace, read the peak memory and
    free the program. ``chip_check=False`` runs on whatever devices JAX
    has and reads no peaks (the tests' hook)."""
    import jax
    devices = device_check(cell.chips) if chip_check \
        else jax.devices()[:cell.chips]
    if chip_check:
        enable_compile_cache()
    peaks = check_mod.peaks_for(check_mod.load_peaks(cell.root),
                                devices[0].device_kind) if chip_check else {}
    dims = cell.arch().dims(cell.config)
    engine = build_program(cell, seed)
    warm_up(engine, cell, seed)
    specs = traffic.generate(cell.traffic, rate or cell.load["rate_per_s"],
                             seconds, seed, cell.config["vocab_size"])
    reqs = to_requests(specs)
    counter = CompileCounter()
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    tracer = None
    if trace:
        start = TRACE_START * seconds
        tracer = Tracer(tdir, t0, start,
                        min(seconds, start + TRACE_MAX_S))
        tracer.start()
    sampler = StackSampler(t0)
    sampler.start()
    counter.active = True
    report = engine.serve(reqs, seed=seed % 2 ** 31, realtime=True)
    counter.active = False
    sampler.done.set()
    sampler.join()
    traced = None
    if tracer is not None:
        tracer.join()
        if tracer.error is not None:
            raise tracer.error
        traced = reduce_trace(tdir, tracer.span)
        shutil.rmtree(tdir, ignore_errors=True)
    data = run_data(cell, specs, report, seconds, setup_s, traced, dims,
                    peaks)
    for a, b, _ in data.stalls():
        named = [round(a, 3), round(b, 3), sampler.within(a, b)]
        print(f"stall: {json.dumps(named)}", file=sys.stderr, flush=True)
    mem = memory_peak_bytes(devices) if chip_check else 0
    sample = check_mod.sample_greedy(specs, report.sequences, seed,
                                     cell.load["check"]["sample"])
    del engine, report          # the reference needs the memory
    gc.collect()
    return Window(data=data, sample=sample, window_compiles=counter.count,
                  devices=devices, memory_peak_bytes=mem)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_process: float, chip_check: bool = True,
             control: bool = False) -> dict:
    """One run of ``cell``; returns the result object the command
    prints. ``chip_check=False`` is the tests' hook: the run goes ahead
    on whatever devices JAX has, and the result reports no metrics —
    a CPU number never appears under a device metric's name.
    ``control=True`` (``bench/calibrate.py`` and the tests, never a
    benchmark run) puts the float8 control in the program's place for the
    check: the tokens it ranks first are judged as if they were served,
    and the result adds the readings of both."""
    w = serve_window(cell, seed, seconds, trace, t_process, chip_check)
    data, devices = w.data, w.devices
    metrics = {}
    if chip_check:
        metrics = spec.read_metrics(
            cell.root, cell.per_layer if trace else cell.end_to_end, data)
    print(f"window: {json.dumps(data.step_summary())}", file=sys.stderr,
          flush=True)
    checks, readings = check_mod.run_checks(cell, seed, w.sample, data,
                                            w.window_compiles, control)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": w.memory_peak_bytes}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": len(data.requests),
              "failed": sum(1 for r in data.requests if not r.completed),
              "metrics": metrics, "device": device}
    traced = data.traced
    if traced is not None:
        device["busy_s"] = traced.mean_busy_s
        device["window_s"] = traced.window_s
        result["breakdown"] = {"device_ops": [list(x) for x in
                                              traced.top_ops(10)],
                               "idle_gaps": [list(x) for x in traced.gaps]}
    if control:
        result["readings"] = readings
    result["checks"] = checks
    return result
