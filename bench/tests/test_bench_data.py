"""``BENCHMARK.json`` against its files: every name resolves, every metric
is reported where it should be, and new cells are only files plus entries."""
import filecmp
import json
import os
import re
import shutil
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

from harness import cell, check, cost, spec

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load()


def test_every_cell_resolves_to_its_files(bench):
    doc = bench.doc
    for w in doc["workloads"]:
        assert w["chips"] in (1, 4)
        cfg = bench.config(w["config"])
        assert cfg["name"] == w["config"]
        mix = bench.traffic(w["traffic"])
        gen = bench.generator(mix["kind"])
        assert gen.DRIVE in ("open", "closed")
        assert callable(gen.plan if gen.DRIVE == "open" else gen.stream)
        model = mix.get("erasures", {"model": "none"})["model"]
        assert model == "none" or callable(bench.generator(model).erasures)
        for m in bench.metrics(w["name"], "end_to_end") + bench.metrics(w["name"], "per_layer"):
            assert callable(bench.reader(m["name"]).read), m["name"]
    for c in doc["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert c["file"].startswith("bench/") and data["name"] == c["name"]
        for key in c["reduced"]:
            assert key in data and key in data["reduced_from"], key
        assert "limits" in data and "engine" in data


def test_metrics_move_what_their_cells_report(bench):
    doc = bench.doc
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    cells = [w["name"] for w in doc["workloads"]]
    assert "setup_s" in e2e
    for m in doc["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for c in m.get("workloads", cells):
            assert c in cells
            assert m["moves"] in [x["name"] for x in bench.metrics(c, "end_to_end")], (
                m["name"], c)
    for c in cells:
        names = [x["name"] for x in bench.metrics(c, "end_to_end")]
        assert "setup_s" in names and len(names) >= 2
        assert bench.metrics(c, "per_layer")


def test_names_units_and_shape_of_the_file(bench):
    doc = bench.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in doc[k]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in doc["workloads"]]:
        assert NAME.match(n), n
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in doc["per_layer"]:
        assert "_roofline" not in m["name"] or m["unit"] == "%"
    assert 1 <= doc["run_seconds"] <= 51
    assert os.path.getsize(ROOT / "BENCHMARK.json") <= 64 * 1024


def test_a_new_cell_is_files_plus_entries(tmp_path):
    """A configuration, a mix with an arrival kind of its own and a metric
    added as new files and entries, with no existing file edited, are
    found by name."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "phi3-mini-3.8b.json").read_text())
    cfg["name"] = "phi3-mini-3.8b-s1024"
    (b / "configs" / "phi3-mini-3.8b-s1024.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "chat.json").read_text())
    (b / "traffic" / "chat-slow.json").write_text(
        json.dumps({**mix, "kind": "even", "rate_per_s": 1.0}))
    (b / "traffic" / "even.py").write_text(
        "from harness.traffic import Request, prompt, rng\n"
        "DRIVE = 'open'\n"
        "def plan(mix, seed, seconds, vocab, t_start):\n"
        "    g, r = rng(seed, 2), mix['rate_per_s']\n"
        "    reqs = [Request(i, prompt(g, 8, vocab), 4, t_start + i / r, 'window')\n"
        "            for i in range(int(r * seconds))]\n"
        "    return reqs, (t_start, t_start + seconds)\n")
    (b / "metrics" / "engine.splices.py").write_text("def read(run):\n    return 1.0\n")
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": cfg["name"], "source": "https://example.org",
                           "file": "bench/configs/phi3-mini-3.8b-s1024.json",
                           "reduced": [], "why": "x"})
    doc["workloads"].append({"name": "phi3-chat-slow", "config": cfg["name"],
                             "traffic": "chat-slow", "chips": 1, "why": "x"})
    doc["per_layer"].append({"name": "engine.splices", "unit": "1", "better": "lower",
                             "source": "program_counter", "layer": "engine",
                             "moves": "tokens_per_s", "workloads": ["phi3-chat-slow"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = spec.load(tmp_path)
    assert bench.config(bench.cell("phi3-chat-slow")["config"])["name"] == cfg["name"]
    assert bench.traffic("chat-slow")["rate_per_s"] == 1.0
    reqs, window = bench.generator("even").plan(bench.traffic("chat-slow"), 5, 3.0, 512, 2.0)
    assert [r.t_due for r in reqs] == [2.0, 3.0, 4.0] and window == (2.0, 5.0)
    assert [m["name"] for m in bench.metrics("phi3-chat-slow", "per_layer")] == ["engine.splices"]
    assert bench.reader("engine.splices").read(None) == 1.0


STUB_REFERENCE = """
import numpy as np

CALLS = []


def logit_gaps(cfg, seed, prompts, served, s_pad, control=False):
    CALLS.append({"config": cfg["name"], "seed": seed, "requests": len(prompts),
                  "s_pad": s_pad, "control": control})
    gaps = [np.zeros(len(t), np.float32) for t in served]
    return gaps, (gaps if control else None)


def last_logits(cfg, seed, seqs, s_pad):
    return np.zeros((len(seqs), cfg["vocab"]), np.float32)
"""
# An attention-free family (no heads): what a configuration of a second
# family brings, at a size the CPU runs in seconds.
SSM = {"name": "ssm-tiny", "family": "ssm", "reference": "stub", "n_layers": 2,
       "d_model": 64, "n_heads": 0, "n_kv_heads": 0, "d_ff": 0, "vocab": 512,
       "ssm_state": 16, "ssm_head_dim": 8, "ssm_chunk": 16, "param_dtype": "float32",
       "dtype": "float32", "coded": True, "coded_parity": 2,
       "engine": {"n_slots": 4, "s_max": 96, "macro_steps": 1, "head_kernel_mode": None},
       "limits": {"max_logit_gap": 0.04}}


def _root_with_new_family(tmp_path, cfg, dirs=("harness", "references")):
    """A checkout holding ``BENCHMARK.json`` and the named ``bench/`` dirs,
    plus a configuration of a new family, its stub reference and a cell
    on the chat mix: new files and entries only."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for d in dirs:
        shutil.copytree(ROOT / "bench" / d, tmp_path / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench" / "references" / "stub.py").write_text(STUB_REFERENCE)
    (tmp_path / "bench" / "configs").mkdir(exist_ok=True)
    (tmp_path / "bench" / "configs" / "ssm-tiny.json").write_text(json.dumps(cfg))
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "ssm-tiny", "source": "https://example.org",
                           "file": "bench/configs/ssm-tiny.json", "reduced": [],
                           "why": "x"})
    doc["workloads"].append({"name": "ssm-chat", "config": "ssm-tiny",
                             "traffic": "chat", "chips": 1, "why": "x"})
    for m in doc["end_to_end"]:
        if m["name"] in ("itl_p95_ms", "ttft_p90_ms"):
            m["workloads"].append("ssm-chat")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    return spec.load(tmp_path)


def _unedited(tmp_path, *dirs):
    for d in dirs:
        cmp = filecmp.dircmp(ROOT / "bench" / d, tmp_path / "bench" / d,
                             ignore=["__pycache__"])
        assert not cmp.diff_files and not cmp.left_only, (d, cmp.diff_files)


def test_a_reference_is_a_new_file(tmp_path):
    """A configuration names a reference module that is a new file, and
    ``run_check`` calls that module, with no harness file edited."""
    bench = _root_with_new_family(tmp_path, SSM)
    cfg = bench.config("ssm-tiny")
    served = [SimpleNamespace(uid=u, n_prompt=n, prompt=np.zeros(n, np.int32),
                              tokens=[1] * k, max_new=k)
              for u, (n, k) in enumerate([(16, 5), (32, 9), (16, 3)])]
    mix = spec.load().traffic("chat")
    checks, correct, diag = check.run_check(bench, cfg, mix, 7, served, control=True)
    stub = bench.reference(cfg)
    assert stub.__file__ == str(tmp_path / "bench" / "references" / "stub.py")
    assert stub.CALLS == [{"config": "ssm-tiny", "seed": 7,
                           "requests": min(3, mix["check_requests"]),
                           "s_pad": check.padded_length(mix), "control": True}]
    assert correct and checks["max_logit_gap"]["value"] == 0.0
    assert diag["checked_tokens"] == 17
    _unedited(tmp_path, "harness", "references")


def test_a_configuration_without_a_reference_is_refused(tmp_path):
    cfg = {k: v for k, v in SSM.items() if k != "reference"}
    bench = _root_with_new_family(tmp_path, cfg)
    with pytest.raises(KeyError, match='names no "reference"'):
        bench.reference(bench.config("ssm-tiny"))
    with pytest.raises(KeyError, match='names no "reference"'):
        cell.run("ssm-chat", 3, 1.0, False, t_process=time.monotonic(), bench=bench)


def test_a_family_without_heads_runs_end_to_end(tmp_path, monkeypatch):
    """Set-up, the window and the check of a cell whose configuration has
    no attention heads (``n_heads`` 0) never build the dense family's
    shape: only the readers that count dense attention do."""
    def refuse(*a, **kw):
        raise AssertionError("DenseShape built for a family without heads")

    monkeypatch.setattr(cost.DenseShape, "of", refuse)
    bench = _root_with_new_family(tmp_path, SSM, dirs=("harness", "references",
                                                       "traffic", "metrics"))
    mix = {"rate_per_s": 8.0, "warm_s": 0.3, "drain_s": 5, "check_requests": 4,
           "prompt": {"median": 24, "sigma": 0.5, "min": 16, "max": 48, "round_to": 16},
           "output": {"median": 12, "sigma": 0.3, "min": 8, "max": 20}}
    out = cell.run("ssm-chat", 2**33 + 5, 1.0, False, t_process=time.monotonic(),
                   bench=bench, overrides={"traffic": mix})
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"itl_p95_ms", "ttft_p90_ms", "setup_s"}
    assert out["metrics"]["ttft_p90_ms"]["value"] > 0
    calls = bench.reference(SSM).CALLS
    assert len(calls) == 1 and calls[0]["requests"] == 4
    _unedited(tmp_path, "harness", "references", "traffic", "metrics")


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k not in ("PYTHONPATH",)}
    env.update({"JAX_PLATFORMS": "cpu"}, **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi3-chat", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
