"""One run of one cell: set up, warm, measure, check, reduce.

``run()`` builds the configuration's model through the program's own
registry, makes its weights on the device from the seed, drives
``ServeEngine.macro_step()`` with the cell's traffic, and returns the
result line.  It does not look for a chip: ``bench/run.py`` does that
first, and tests call ``run()`` directly on the CPU at small sizes.

Timeline of a run (seconds on the bench's clock):

* set-up: weights, the engine, then warm groups: 1, 2, ... requests
  admitted together with every prompt length of the mix, so every prefill
  length and every admission count the window can meet compiles here;
  then ``warm_s`` of the cell's own traffic;
* the window: ``--seconds`` of the same traffic, measured;
* open loops drain: arrivals go on, until every request due in the window
  has finished or ``drain_s`` has passed (unfinished ones have failed);
* the check: the program's state is freed and the reference scores a
  sample of the finished requests (``bench/harness/check.py``).

With ``trace`` the last ``TRACE_S`` seconds of the window are traced, and
the per-layer metrics are read from that trace and the run's records.
"""
from __future__ import annotations

import gc
import shutil
import time
from collections import defaultdict
from dataclasses import dataclass, field, fields
from typing import Any

import numpy as np

import jax

from . import check, cost, spec, traffic
from . import trace as tr
from .compilelog import CompileLog

__all__ = ["run", "Run", "TRACE_S"]

TRACE_S = 3.0           # traced seconds at the end of the window
PROBE_CALLS = 32        # bench-driven calls of a kernel in a traced probe


@dataclass
class Rec:
    """One request as the bench saw it (times on the bench's clock)."""
    uid: int
    section: str
    t_due: float | None
    n_prompt: int
    max_new: int
    prompt: np.ndarray
    tokens: list[int] = field(default_factory=list)
    times: list[float] = field(default_factory=list)
    t_admit: float | None = None

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new


@dataclass
class Step:
    """One ``macro_step`` call: its span, the prompt lengths it prefilled
    and the context position of each token it decoded."""
    t0: float
    t1: float
    prefill: list[int]
    decode_pos: list[int]


@dataclass
class Run:
    """What a metric reader may read."""
    cell: dict
    cfg: dict
    mix: dict
    seconds: float
    setup_s: float
    window: tuple[float, float]
    recs: list[Rec]
    steps: list[Step]
    shape: cost.DenseShape
    params: Any                       # ShapeDtypeStruct tree of the weights
    cache: Any                        # ShapeDtypeStruct tree of the cache
    device_kind: str
    traced: tuple[float, float] | None = None
    trace: tr.Trace | None = None
    probes: dict = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def peaks(self) -> cost.Peaks:
        return cost.peaks(self.device_kind)

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t <= self.window[1]

    def steps_in(self, span: tuple[float, float]) -> list[Step]:
        return [s for s in self.steps if s.t0 >= span[0] and s.t1 <= span[1]]


class Clock:
    """The engine's clock: a virtual one while warm groups run, then
    monotonic seconds continuing from where the virtual one stood."""

    def __init__(self):
        self.virtual = 0.0
        self.base: float | None = None

    def go_real(self) -> None:
        self.base = time.monotonic() - self.virtual

    def __call__(self) -> float:
        return self.virtual if self.base is None else time.monotonic() - self.base


def _model_config(cfg: dict):
    from repro.models.config import ModelConfig

    names = {f.name for f in fields(ModelConfig)}
    return ModelConfig(**{k: v for k, v in cfg.items() if k in names})


def _sds(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


class Ledger:
    """Per-step bookkeeping from the engine's public request state."""

    def __init__(self, eng):
        self.eng = eng
        self.seen: dict[int, int] = {}
        self.n_done = 0
        self.steps: list[Step] = []
        self.stamps: dict[int, list[float]] = defaultdict(list)

    def step(self, clock) -> int:
        eng = self.eng
        before = [r for r in eng.slots if r is not None]
        t0 = clock()
        with jax.profiler.TraceAnnotation("bench.step"):
            busy = eng.macro_step()
        t1 = clock()
        live = {id(r): r for r in before + [r for r in eng.slots if r is not None]
                + eng.completed[self.n_done:]}
        self.n_done = len(eng.completed)
        pre, dec = [], []
        for r in live.values():
            n0, n1 = self.seen.get(r.uid, 0), len(r.out_tokens)
            for i in range(n0, n1):
                if i == 0:
                    pre.append(len(r.prompt))
                else:
                    dec.append(len(r.prompt) + i - 1)
                self.stamps[r.uid].append(t1)
            self.seen[r.uid] = n1
        self.steps.append(Step(t0, t1, pre, dec))
        return busy


def _engine(model, params, cfg, mask_fn, **kw):
    from repro.serve import ServeEngine

    e = cfg["engine"]
    return ServeEngine(model, params, n_slots=e["n_slots"], s_max=e["s_max"],
                       mask_fn=mask_fn, macro_steps=e.get("macro_steps", 1),
                       head_kernel_mode=e.get("head_kernel_mode"), **kw)


class _Tracer:
    """Starts the profiler at ``t_on`` and stops it after ``t_off``, with
    the ``bench.window`` span around what it recorded."""

    def __init__(self, log_dir, t_on: float, t_off: float):
        self.dir, self.t_on, self.t_off = log_dir, t_on, t_off
        self.span = None
        self.state = "before"
        self.traced: tuple[float, float] | None = None

    def poll(self, now: float) -> None:
        if self.state == "before" and now >= self.t_on:
            start_trace(self.dir)
            self.span = jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()
            self.traced = (now, now)
            self.state = "on"
        elif self.state == "on" and now >= self.t_off:
            self.span.__exit__(None, None, None)
            self.traced = (self.traced[0], now)
            jax.profiler.stop_trace()
            self.state = "done"


def start_trace(log_dir) -> None:
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def _drive_open(gen, model, params, cfg, mix, seed, seconds, mask_fn, tracer_at,
                log, marks):
    from repro.serve import ArrivalTrace, Request, TraceScheduler

    class TimedScheduler(TraceScheduler):
        """Records the engine's stamp of every token it reports."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.times: dict[int, list[float]] = defaultdict(list)

        def on_token(self, idx, now):
            self.times[idx].append(now)
            return super().on_token(idx, now)

    vocab, n_slots = cfg["vocab"], cfg["engine"]["n_slots"]
    groups = traffic.warm_groups(mix, n_slots, vocab, seed)
    plan, window = gen.plan(mix, seed, seconds, vocab, float(len(groups)))
    reqs = [r for g in groups for r in g] + plan
    due = [float(i) for i, g in enumerate(groups) for _ in g] + [r.t_due for r in plan]
    recs = [Rec(i, r.section, d, len(r.prompt), r.max_new, r.prompt)
            for i, (r, d) in enumerate(zip(reqs, due))]
    arr = ArrivalTrace(t_arrival=np.array(due), n_tokens=np.array([r.max_new for r in reqs]),
                       deadline=np.array(due) + 1e9)
    payloads = [Request(uid=i, prompt=r.prompt, max_new_tokens=r.max_new)
                for i, r in enumerate(reqs)]
    sched = TimedScheduler(arr, n_slots, admission="all", payloads=payloads)
    clock = Clock()
    eng = _engine(model, params, cfg, mask_fn, scheduler=sched, clock=clock)
    led = Ledger(eng)
    for g in range(len(groups)):
        clock.virtual = float(g)
        while led.step(clock) > 0 or sched.pending(clock()) > 0:
            pass
    clock.virtual = float(len(groups))
    clock.go_real()
    t0, t1 = window
    tracer = tracer_at(t0, t1)
    t_end = t1 + mix["drain_s"]
    in_win = [i for i, r in enumerate(recs) if r.section == "window"]
    marks["t_warm"] = time.monotonic()
    marks["warm"] = log.mark()
    while True:
        now = clock()
        if "window" not in marks and now >= t0:
            marks["window"] = log.mark()
        if "window_end" not in marks and now >= t1:
            marks["window_end"] = log.mark()
        if tracer:
            tracer.poll(now)
        if now >= t_end or (now >= t1 and all(sched.requests[i].done for i in in_win)):
            break
        busy = led.step(clock)
        if busy == 0:
            nxt = sched.next_arrival()
            if nxt is None:
                break
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(nxt, t_end) - clock()))
    marks.setdefault("window_end", log.mark())
    for r in recs:
        sr = sched.requests[r.uid]
        r.tokens = list(payloads[r.uid].out_tokens)
        r.times = sched.times.get(r.uid, [])
        r.t_admit = float(sr.t_admit) if sr.admitted else None
    setup_end = clock.base + t0
    return eng, recs, led.steps, window, setup_end, tracer


def _drive_closed(gen, model, params, cfg, mix, seed, seconds, mask_fn,
                  tracer_at, log, marks):
    from repro.serve import Request

    vocab, n_slots = cfg["vocab"], cfg["engine"]["n_slots"]
    clock = time.monotonic
    eng = _engine(model, params, cfg, mask_fn)
    led = Ledger(eng)
    recs: dict[int, Rec] = {}

    def submit(r: traffic.Request) -> None:
        recs[r.uid] = Rec(r.uid, r.section, None, len(r.prompt), r.max_new, r.prompt)
        eng.submit(Request(uid=r.uid, prompt=r.prompt, max_new_tokens=r.max_new))

    for grp in traffic.warm_groups(mix, n_slots, vocab, seed):
        for r in grp:
            submit(r)
        while led.step(clock) > 0 or eng.queue:
            pass
    stream = gen.stream(mix, seed, vocab, n_slots)
    depth = n_slots + int(mix["queue_extra"])
    t_warm = clock()
    t0 = t_warm + mix["warm_s"]
    t1 = t0 + seconds
    tracer = tracer_at(t0, t1)
    marks["t_warm"] = time.monotonic()
    marks["warm"] = log.mark()
    while True:
        now = clock()
        if "window" not in marks and now >= t0:
            marks["window"] = log.mark()
        if tracer:
            tracer.poll(now)
        if now >= t1:
            break
        while len(eng.queue) < depth:
            submit(next(stream))
        led.step(clock)
    marks["window_end"] = log.mark()
    by_uid = {r.uid: r for r in eng.completed}
    by_uid.update({r.uid: r for r in eng.slots if r is not None})
    for uid, rec in recs.items():
        if uid in by_uid:
            rec.tokens = list(by_uid[uid].out_tokens)
            rec.times = led.stamps.get(uid, [])
    return eng, list(recs.values()), led.steps, (t0, t1), t0, tracer


def _probe_coded_head(bench, params, cfg, mix, seed, n_blocks, log_dir):
    """The program's coded-head matvec, called by the bench at the cell's
    batch with the cell's erasure masks, traced on its own."""
    import jax.numpy as jnp

    from repro.kernels.ops import coded_head_matvec

    n_par = cfg["coded_parity"]
    n_data = n_blocks - n_par
    b = cfg["engine"]["n_slots"]

    def coded_head_probe(w, x, mask):
        return coded_head_matvec(w, x, mask, n_data, n_par)

    fn = jax.jit(coded_head_probe)
    w = params["lm_head_coded"]
    x = jax.random.normal(jax.random.key(seed % 2**32), (w.shape[1], b), jnp.float32)
    src = traffic.erasure_fn(bench, mix, n_blocks, n_par, seed + 1)
    masks = [jnp.asarray(src() if src else np.ones(n_blocks, np.float32))
             for _ in range(PROBE_CALLS)]
    jax.block_until_ready(fn(w, x, masks[0]))  # compiled in set-up
    return lambda: _run_probe(fn, w, x, masks, log_dir)


def _run_probe(fn, w, x, masks, log_dir):
    start_trace(log_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        outs = [fn(w, x, m) for m in masks]
        jax.block_until_ready(outs)
    jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(log_dir)))
    shutil.rmtree(log_dir, ignore_errors=True)
    return {"seconds": tr.module_seconds(t, "coded_head_probe"),
            "w": jax.ShapeDtypeStruct(w.shape, w.dtype), "batch": x.shape[1]}


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, bench: spec.Bench | None = None,
        overrides: dict | None = None, control: bool = False) -> dict:
    """One run of ``workload``; the result line as a dict.  ``overrides``
    replaces keys of the configuration (``"config"``) and of the mix
    (``"traffic"``); ``control`` also reads the float8 control on the
    same sample (``bench/readings.py``; the benchmark's runs never do)."""
    from repro.models.config import coded_blocks
    from repro.models.registry import build_model

    from reference import seed_key

    t_run = time.monotonic()
    bench = bench or spec.load()
    overrides = overrides or {}
    cell = bench.cell(workload)
    cfg = {**bench.config(cell["config"]), **overrides.get("config", {})}
    mix = {**bench.traffic(cell["traffic"]), **overrides.get("traffic", {})}
    log = CompileLog()
    dev = jax.devices()[0]
    mcfg = _model_config(cfg)
    model = build_model(mcfg)
    n_blocks = coded_blocks(mcfg) if mcfg.coded else 0
    params = jax.block_until_ready(jax.jit(model.init)(seed_key(seed)))
    t_weights = time.monotonic()
    shape = cost.DenseShape.of(cfg, n_blocks)
    mask_fn = (traffic.erasure_fn(bench, mix, n_blocks, mcfg.coded_parity, seed)
               if mcfg.coded else None)
    gen = bench.generator(mix["kind"])

    per_layer = bench.metrics(workload, "per_layer") if trace else []
    readers = {m["name"]: bench.reader(m["name"]) for m in per_layer}
    want = {p for r in readers.values() for p in getattr(r, "PROBES", ())}
    trace_dir = bench.root / ".bench_trace"
    probes = {}
    if "coded_head" in want and mcfg.coded:
        probes["coded_head"] = _probe_coded_head(bench, params, cfg, mix, seed,
                                                 n_blocks, trace_dir / "probe")

    def tracer_at(t0, t1):
        if not trace:
            return None
        return _Tracer(trace_dir / "window", max(t0, t1 - TRACE_S), t1)

    marks: dict = {}
    drive = {"open": _drive_open, "closed": _drive_closed}[gen.DRIVE]
    eng, recs, steps, window, setup_end, tracer = drive(
        gen, model, params, cfg, mix, seed, seconds, mask_fn, tracer_at, log, marks)
    if tracer and tracer.state == "on":
        tracer.poll(float("inf"))
    setup_s = setup_end - t_process
    probe_out = {k: f() for k, f in probes.items()}
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    params_sds, cache_sds = _sds(params), _sds(eng.cache)
    del eng, params, probes
    gc.collect()

    finished = [r for r in recs if r.section != "warm0" and r.done and r.times
                and window[0] <= r.times[-1] <= window[1] + mix.get("drain_s", 0.0)]
    checks, correct, diag = check.run_check(cfg, mix, seed, finished, control)

    t = None
    if tracer and tracer.traced:
        t = tr.load(tr.find_xplane(str(trace_dir / "window")))
        shutil.rmtree(trace_dir, ignore_errors=True)
    r = Run(cell, cfg, mix, seconds, setup_s, window, recs, steps, shape,
            params_sds, cache_sds, dev.device_kind,
            tracer.traced if tracer else None, t, probe_out)

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench.metrics(workload, kind):
        rd = readers.get(m["name"]) or bench.reader(m["name"])
        v = rd.read(r)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    if gen.DRIVE == "open":
        due = [x for x in recs if x.section == "window"]
        attempted, failed = len(due), sum(not x.done for x in due)
        waits = [1e3 * (x.t_admit - x.t_due) if x.t_admit is not None else None
                 for x in due]
        diag["tokens_in_window"] = sum(1 for x in recs if x.section != "warm0"
                                       for t in x.times if window[0] <= t <= window[1])
        diag["queue_wait_ms_by_third"] = [
            float(np.mean([w for w in part if w is not None] or [np.nan]))
            for part in np.array_split(np.array(waits, dtype=object), 3)]
    else:
        attempted = sum(1 for x in recs if x.done and x.times
                        and window[0] <= x.times[-1] <= window[1])
        failed = 0
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if t is not None:
        device["busy_s"] = tr.busy_s(t)
        device["window_s"] = t.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in tr.top_ops(t)],
                            "idle_gaps": [list(x) for x in tr.gaps_by_host(t)[:10]]}
    out["compiles"] = {k: _between(marks, a, b) for k, a, b in (
        ("set-up", None, "warm"), ("warm", "warm", "window"),
        ("window", "window", "window_end"))}
    diag["setup_parts_s"] = {
        "start_to_run": t_run - t_process, "weights": t_weights - t_run,
        "engine_and_warm_groups": marks["t_warm"] - t_weights,
        "warm_traffic": setup_end - marks["t_warm"]}
    out["diag"] = diag
    out["checks"] = checks
    return out


def _between(marks: dict, a: str | None, b: str) -> dict | None:
    """Programs loaded and compiled between two marks of the CompileLog."""
    m0 = marks.get(a) if a else (0, 0.0, 0)
    m1 = marks.get(b)
    if m0 is None or m1 is None:
        return None
    return {"loaded": m1[0] - m0[0], "compiled": (m1[0] - m0[0]) - (m1[2] - m0[2]),
            "load_s": m1[1] - m0[1]}
