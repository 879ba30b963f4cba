"""What a layer's reader sees of the program: its named scopes through
``readers.scope_ms``, its host spans through ``readers.idle_under_ms``,
and its counters through ``Run.counters``; on hand-made traces and in
whole runs at a small size on the CPU."""
import json
import shutil
import time
from types import SimpleNamespace

import pytest

import jax

from harness import cell, cost, readers, spec
from harness import trace as tr

from .test_bench_data import _unedited
from .test_bench_faults import CELLS, SMALL

ev = tr.Event


def _run(trace, op_scopes=None, steps=()):
    """What the readers under test read of a ``cell.Run``."""
    return SimpleNamespace(trace=trace, op_scopes=op_scopes or {}, traced=(0.0, 1.0),
                           steps_in=lambda span: list(steps))


def _decode_step():
    return cell.Step(0.0, 0.0, [], [7])


# -- scope_ms ---------------------------------------------------------------

MAP = {"f": {"while.1": "jit(f)/layers/while",
             "fusion.1": "jit(f)/layers/while/body/inner/dot_general",
             "fusion.2": "jit(f)/layers/while/body/inner/tanh",
             "copy.1": "jit(f)/head/copy", "copy.2": ""}}


def _hand_trace():
    """Two runs of ``jit_f`` in the window, a third that ends after it;
    each: a ``while`` (container) over two body fusions, then a copy."""
    ops, mods = [], []
    for t in (0, 200, 950):
        mods.append(ev("jit_f(1)", t, t + 100))
        ops += [ev("while.1", t, t + 80), ev("fusion.1", t + 10, t + 40),
                ev("fusion.2", t + 40, t + 70), ev("copy.1", t + 80, t + 95),
                ev("copy.2", t + 95, t + 99)]
    return tr.Trace([tr.Device(ops, mods)], [], (0.0, 1000.0))


def test_scope_ms_by_hand():
    r = _run(_hand_trace(), MAP)
    # the while's body once, not the while around it as well
    assert readers.scope_ms(r, "f", "layers") == pytest.approx(60e-6)
    assert readers.scope_ms(r, "f", "inner") == pytest.approx(60e-6)
    assert readers.scope_ms(r, "f", "head") == pytest.approx(15e-6)
    assert readers.scope_ms(r, "f", "in") is None       # a segment, not a prefix
    assert readers.scope_ms(r, "f", "kv_write") is None
    assert readers.scope_ms(r, "g", "inner") is None    # no map of that program
    assert readers.scope_ms(_run(None, MAP), "f", "inner") is None


# -- idle_under_ms -----------------------------------------------------------

def test_idle_is_charged_to_the_innermost_engine_span():
    host = [ev("bench.step", 0, 100), ev("engine.step", 5, 95),
            ev("engine.sync", 50, 90), ev("engine.apply", 90, 95)]
    dev = tr.Device(ops=[ev("fusion.1", 0, 40), ev("fusion.2", 60, 70)],
                    modules=[ev("jit_f(1)", 0, 70)])
    t = tr.Trace([dev], host, (0.0, 100.0))
    assert dict(tr.gaps_by_host(t)) == pytest.approx(
        {"engine.sync": 30e-9, "engine.step": 10e-9, "engine.apply": 5e-9,
         "bench.step": 5e-9})
    steps = [_decode_step(), cell.Step(0.0, 0.0, [16], []), _decode_step()]
    r = _run(t, steps=steps)
    assert readers.idle_under_ms(r, "engine.sync") == pytest.approx(30e-6 / 2)
    assert readers.idle_under_ms(r, "engine.launch") is None  # no such span
    assert readers.idle_under_ms(_run(t), "engine.sync") is None  # no decode
    assert readers.idle_under_ms(_run(None, steps=steps), "engine.sync") is None


# -- whole runs on the CPU ---------------------------------------------------

STUB_SCOPE = """from harness.readers import scope_ms


def read(run):
    return scope_ms(run, "_decode_argmax", "stub_family")
"""
STUB_COUNTER = """def read(run):
    c0, c1 = run.counters["stub_steps"]
    return c1 - c0
"""


def _small(workload, bench, monkeypatch, trace, seed):
    monkeypatch.setitem(cost.PEAKS, "cpu", cost.PEAKS["TPU v5 lite"])
    over, mix = CELLS[workload]
    return cell.run(workload, seed, 1.0, trace, t_process=time.monotonic(), bench=bench,
                    overrides={"config": {**SMALL, **over}, "traffic": mix})


def test_a_reader_of_a_new_scope_and_counter_is_new_files(tmp_path, monkeypatch):
    """A family's own named scope and engine counter (made up here: the
    whole decode step under ``stub_family``, a ``stub_steps`` count of
    steps) reach two new reader files in a traced run, with no harness
    file edited.  The CPU has no device plane, so the trace gets one
    whose operations are the compiled step's own, 10 ns each."""
    from repro.models import transformer
    from repro.serve import ServeEngine

    step = transformer.lm_decode_step

    def scoped(*a, **kw):
        with jax.named_scope("stub_family"):
            return step(*a, **kw)

    monkeypatch.setattr(transformer, "lm_decode_step", scoped)
    macro = ServeEngine.macro_step

    def counted(self):
        self.stub_steps = getattr(self, "stub_steps", 0) + 1
        return macro(self)

    monkeypatch.setattr(ServeEngine, "macro_step", counted)
    maps, traces = {}, []
    op_scopes = ServeEngine.op_scopes

    def kept(self):
        maps.update(op_scopes(self))
        return maps

    monkeypatch.setattr(ServeEngine, "op_scopes", kept)
    load = tr.load

    def with_device(path):
        t = load(path)
        names = sorted(maps["_decode_argmax"])
        ops, mods = [], []
        for k in range(3):
            t0 = t.window[0] + (k + 1) * 1e6
            mods.append(ev("jit__decode_argmax(1)", t0, t0 + 10 * len(names)))
            ops += [ev(n, t0 + 10 * i, t0 + 10 * i + 10) for i, n in enumerate(names)]
        t.devices = [tr.Device(ops, mods)]
        traces.append(t)
        return t

    monkeypatch.setattr(tr, "load", with_device)

    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(spec.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench" / "metrics" / "stub.scope_ms.py").write_text(STUB_SCOPE)
    (tmp_path / "bench" / "metrics" / "stub.steps.py").write_text(STUB_COUNTER)
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for name, unit in (("stub.scope_ms", "ms"), ("stub.steps", "1")):
        doc["per_layer"].append({"name": name, "unit": unit, "better": "lower",
                                 "source": "device_trace", "layer": "stub family",
                                 "moves": "tokens_per_s", "workloads": ["glm4-batch"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    out = _small("glm4-batch", spec.load(tmp_path), monkeypatch, True, 2**33 + 17)
    assert out["correct"], out["checks"]
    scoped_ops = [i for i, op in maps["_decode_argmax"].items()
                  if "stub_family" in op.split("/")]
    assert scoped_ops
    assert out["metrics"]["stub.scope_ms"]["value"] == pytest.approx(10e-6 * len(scoped_ops))
    assert out["metrics"]["stub.steps"]["value"] > 0
    assert out["diag"]["op_scopes_s"] >= 0
    assert {"engine.sync", "engine.launch"} <= {e.name for e in traces[0].host}
    _unedited(tmp_path, "harness")


def test_an_untraced_run_builds_no_op_scopes(monkeypatch):
    from repro.serve import ServeEngine

    calls = []

    def refuse(*a):
        calls.append(a)
        raise AssertionError("read in an untraced run")

    monkeypatch.setattr(ServeEngine, "op_scopes", refuse)
    monkeypatch.setattr(cell, "engine_counters", refuse)
    out = _small("glm4-batch", spec.load(), monkeypatch, False, 2**33 + 19)
    assert out["correct"], out["checks"]
    assert "op_scopes_s" not in out["diag"] and not calls


def test_coded_head_roofline_reads_the_steps_scope():
    """The least time of the head at the engine's batch, over the time of
    the ``coded_head`` operations of a decode run."""
    w = jax.ShapeDtypeStruct((16 * 40, 64), "float32")
    run = SimpleNamespace(
        trace=tr.Trace([tr.Device([ev("dot.1", 10, 4010), ev("add.1", 4010, 5010)],
                                  [ev("jit__decode_argmax(1)", 0, 6000)])], [], (0.0, 1e4)),
        op_scopes={"_decode_argmax": {"dot.1": "jit(_decode_argmax)/coded_head/dot",
                                      "add.1": "jit(_decode_argmax)/add"}},
        cfg={"coded_parity": 2, "engine": {"n_slots": 8}}, n_blocks=16,
        params={"lm_head_coded": w}, peaks=cost.PEAKS["TPU v5 lite"])
    read = spec.load().reader("coded_head_roofline").read
    least = cost.least_seconds(*cost.coded_head_cost(w, 8, 14, 2), run.peaks)
    assert read(run) == pytest.approx(100.0 * least / 4e-6)
    run.op_scopes = {}
    assert read(run) is None
