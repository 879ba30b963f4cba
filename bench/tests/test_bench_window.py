"""What keeps host stalls out of the measured window, at a small size on
the CPU: the wait for the next arrival returns on time, the window runs
with set-up's objects frozen out of the garbage collector's reach and
compiles nothing, and the program's state is freed before the check."""
import gc
import statistics
import time
import weakref

import pytest

from harness import cell

from .test_bench_faults import CELLS, _run


def test_the_wait_for_an_arrival_returns_on_time():
    late = []
    for i in range(20):
        t = time.monotonic() + 0.002 + 0.0005 * i
        cell.sleep_until(time.monotonic, t)
        late.append(time.monotonic() - t)
    assert min(late) >= 0.0
    assert statistics.median(late) < 1e-3, late


@pytest.mark.parametrize("workload", ["phi3-chat", "glm4-batch"])
def test_the_window_runs_frozen_and_compiles_nothing(workload, monkeypatch):
    assert workload in CELLS
    frozen, engines = [], []
    init, step = cell.Ledger.__init__, cell.Ledger.step

    def init_spy(self, eng):
        engines.append(weakref.ref(eng))
        init(self, eng)

    def step_spy(self, clock):
        frozen.append(gc.get_freeze_count())
        return step(self, clock)

    monkeypatch.setattr(cell.Ledger, "__init__", init_spy)
    monkeypatch.setattr(cell.Ledger, "step", step_spy)
    before = gc.get_freeze_count()  # the interpreter freezes a few at start
    out = _run(workload, monkeypatch, seed=2**34 + 11)
    assert out["correct"], out["checks"]
    # every program the window runs was loaded in set-up
    assert out["compiles"]["window"]["loaded"] == 0, out["compiles"]
    slow = out["diag"]["slow_steps"]
    assert slow["median_ms"] > 0 and slow["max_ms"] >= slow["median_ms"]
    assert slow["n"] >= 0 and slow["s"] >= 0
    # warm groups run before the freeze, the warm traffic and window after
    first = next(i for i, n in enumerate(frozen) if n > before)
    assert first > 0 and all(n > before for n in frozen[first:])
    assert gc.get_freeze_count() <= before
    assert len(engines) == 1 and engines[0]() is None


def test_a_closed_loop_loads_its_first_wave_in_set_up(monkeypatch):
    """A closed batch admits every slot at once when its traffic starts, a
    count beyond the warm groups' largest: the programs of that admission
    load in set-up, and the warm traffic and the window load none."""
    over, mix = CELLS["glm4-batch"]
    monkeypatch.setitem(CELLS, "glm4-batch", (
        {**over, "engine": {"n_slots": 12, "s_max": 96, "macro_steps": 1,
                            "head_kernel_mode": None}}, mix))
    out = _run("glm4-batch", monkeypatch, seed=2**34 + 13)
    assert out["correct"], out["checks"]
    assert out["compiles"]["warm"]["loaded"] == 0, out["compiles"]
    assert out["compiles"]["window"]["loaded"] == 0, out["compiles"]
