"""The trace reduction on a small trace recorded on a TPU v5e.

``data/probe.xplane.pb`` (``record_trace_fixture.py``): inside one
``bench.window`` span, five runs of ``probe_step`` (two bfloat16 2048^2
matmuls) each in a ``bench.step`` span and followed by a 20 ms
``bench.wait`` sleep, then two runs of ``probe_other``."""
from pathlib import Path

import pytest

from harness import trace as tr

FIXTURE = Path(__file__).parent / "data" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def t():
    return tr.load(str(FIXTURE))


def test_planes_and_window(t):
    assert len(t.devices) == 1
    assert {e.name for e in t.host} == {"bench.step", "bench.wait"}
    assert sum(e.name == "bench.step" for e in t.host) == 5
    assert 0.1 < t.window_s < 0.2  # five 20 ms sleeps and the calls


def test_device_clock_is_moved_after_the_launches(t):
    # recorded, the first probe_step starts 1.09 ms before the host's
    # window opened; the least shift that puts every run after its launch
    assert t.devices[0].shift_ns == pytest.approx(1.22421e6)
    first = min(e.start for e in t.devices[0].modules)
    assert first >= t.window[0]


def test_program_times(t):
    step = tr.module_seconds(t, "probe_step")
    other = tr.module_seconds(t, "probe_other")
    assert len(step) == 5 and len(other) == 2
    assert all(1.5e-4 < s < 2.5e-4 for s in step)  # 2 x 17 GFLOP at 197 TFLOP/s
    assert all(s < 5e-5 for s in other)


def test_busy_is_the_union_of_operations(t):
    busy = tr.busy_s(t)
    assert busy <= sum(tr.module_seconds(t, "probe")) + 1e-9
    assert busy > 0.9 * sum(tr.module_seconds(t, "probe_step"))
    gaps = tr.idle_gaps(t)
    assert sum(b - a for a, b in gaps) * 1e-9 == pytest.approx(t.window_s - busy)


def test_idle_gaps_by_host_span(t):
    by = dict(tr.gaps_by_host(t))
    assert by["bench.wait"] > 0.095   # five sleeps of 20 ms, minus wake-up
    assert by["bench.wait"] > 10 * by.get("bench.step", 0.0)
    assert sum(by.values()) == pytest.approx(t.window_s - tr.busy_s(t))


def test_top_ops_are_named_by_program(t):
    ops = tr.top_ops(t)
    assert ops[0][0].startswith("jit_probe_step/")
    assert {n.split("/")[0] for n, _ in ops} == {"jit_probe_step", "jit_probe_other"}
    assert sum(s for _, s in ops) == pytest.approx(tr.busy_s(t), rel=0.05)


def test_a_while_around_its_body_is_not_counted_twice():
    ev = tr.Event
    dev = tr.Device(ops=[ev("while.1", 0, 100), ev("fusion.1", 10, 40),
                         ev("fusion.2", 40, 90), ev("copy.1", 120, 130)],
                    modules=[ev("jit_step(1)", 0, 130)])
    t = tr.Trace([dev], [ev("bench.step", 0, 140)], (0.0, 150.0))
    assert dict(tr.top_ops(t)) == pytest.approx(
        {"jit_step/fusion.2": 50e-9, "jit_step/fusion.1": 30e-9, "jit_step/copy.1": 10e-9})
    assert tr.busy_s(t) == pytest.approx(110e-9)
    assert dict(tr.gaps_by_host(t)) == pytest.approx({"bench.step": 30e-9, "host.other": 10e-9})
