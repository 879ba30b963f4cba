"""The serving engine's profiler spans and the model's named scopes.

A tiny coded engine serves one request under erasure masks while
``jax.profiler`` records; the ``.xplane.pb`` it writes is read back with
``ProfileData``.  The spans must nest as documented in ``serve/engine.py``
and carry their args, ``op_scopes()`` must find the ``coded_head`` and
``kv_write`` scopes in the compiled decode step, and recording must not
change a single token.
"""
import glob
import os

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models.registry import build_model
from repro.serve import Request, ServeEngine

N_BLOCKS = 16  # the serving head's block count (models.config.coded_blocks)
PROMPT = np.arange(3, 10, dtype=np.int32)
MAX_NEW = 9

# each span's innermost enclosing engine span (None: none encloses it)
PARENT = {
    "engine.step": None,
    "engine.admit": "engine.step",
    "engine.prefill": "engine.admit",
    "engine.splice": "engine.admit",
    "engine.control": "engine.step",
    "engine.launch": "engine.step",
    "engine.sync": "engine.step",
    "engine.apply": "engine.step",
}


@pytest.fixture(scope="module")
def coded_model():
    cfg = get_config("phi3-mini-3.8b", smoke=True).scaled(coded=True, coded_parity=2)
    model = build_model(cfg)
    return model, model.init(jax.random.key(0))


def _serve(coded_model, macro_steps, log_dir=None):
    """One request through a one-slot engine with seeded erasure masks
    (2 of 16 blocks a step), under the profiler when ``log_dir`` is given."""
    model, params = coded_model
    rng = np.random.default_rng(5)

    def mask_fn():
        m = np.ones(N_BLOCKS, np.float32)
        m[rng.choice(N_BLOCKS, 2, replace=False)] = 0.0
        return m

    eng = ServeEngine(model, params, n_slots=1, s_max=32, mask_fn=mask_fn,
                      macro_steps=macro_steps)
    eng.submit(Request(uid=41, prompt=PROMPT, max_new_tokens=MAX_NEW))
    if log_dir is None:
        eng.run()
    else:
        with jax.profiler.trace(str(log_dir)):
            eng.run()
    return eng


def _engine_spans(log_dir):
    """(name, start, end, args) of every ``engine.*`` event, by start."""
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _parent(span, spans):
    """The shortest other span that encloses ``span``, or None."""
    outer = [s for s in spans if s is not span and s[1] <= span[1] and s[2] >= span[2]]
    return min(outer, key=lambda s: s[2] - s[1], default=None)


@pytest.fixture(scope="module")
def traced(coded_model, tmp_path_factory):
    log_dir = tmp_path_factory.mktemp("engine_trace")
    eng = _serve(coded_model, 4, log_dir)
    return eng, _engine_spans(log_dir)


def test_every_span_nests_in_its_parent(traced):
    _, spans = traced
    assert {s[0] for s in spans} == set(PARENT)
    for s in spans:
        p = _parent(s, spans)
        assert (p and p[0]) == PARENT[s[0]], (s, p)


def test_spans_carry_their_args(traced):
    eng, spans = traced
    by_name = {}
    for s in spans:
        by_name.setdefault(s[0], []).append(s[3])
    assert by_name["engine.prefill"] == [{"uid": 41, "tokens": len(PROMPT)}]
    assert by_name["engine.splice"] == [{"slots": 1}]
    # a scalar step admits and decodes (2 tokens), then fused blocks of 4
    # and 2 and a last scalar step: K is capped by the budget left, and
    # quantized to a power of two
    ks = [a["k"] for a in by_name["engine.step"]]
    assert ks == [1, 4, 2, 1]
    assert eng.macro_blocks == 2
    assert len(by_name["engine.control"]) == sum(ks) == MAX_NEW - 1
    for name in ("engine.launch", "engine.sync", "engine.apply", "engine.admit"):
        assert len(by_name[name]) == len(ks), name


def test_op_scopes_name_the_coded_head_and_kv_write(traced):
    eng, _ = traced
    scopes = eng.op_scopes()
    assert set(scopes) == {"_decode_argmax", "_prefill_argmax"}
    dec = scopes["_decode_argmax"].values()
    assert any("/coded_head/" in op for op in dec)
    assert any("/kv_write/" in op for op in dec)
    assert any("/coded_head/" in op for op in scopes["_prefill_argmax"].values())


@pytest.mark.parametrize("macro_steps", [1, 4])
def test_tokens_identical_with_the_profiler_on_and_off(coded_model, traced,
                                                       tmp_path, macro_steps):
    if macro_steps == 4:
        on = traced[0]
    else:
        on = _serve(coded_model, macro_steps, tmp_path)
    off = _serve(coded_model, macro_steps)
    assert len(on.completed[0].out_tokens) == MAX_NEW
    assert on.completed[0].out_tokens == off.completed[0].out_tokens


def test_serve_launcher_profiles_the_loop(tmp_path, monkeypatch, capsys):
    """``launch/serve.py --profile DIR`` writes a trace holding the engine's
    spans and prints each compiled step's operations per named scope."""
    from repro.launch import serve

    monkeypatch.setattr("sys.argv", [
        "serve", "--arch", "phi3-mini-3.8b", "--smoke", "--requests", "2",
        "--slots", "2", "--max-new", "3", "--s-max", "32", "--coded",
        "--straggler-prob", "0.2", "--profile", str(tmp_path)])
    serve.main()
    out = capsys.readouterr().out
    assert "_decode_argmax operations: coded_head " in out
    assert "kv_write " in out
    assert {s[0] for s in _engine_spans(tmp_path)} >= {
        "engine.step", "engine.prefill", "engine.launch", "engine.sync"}


def test_a_renamed_scope_is_not_loaded_from_the_compile_cache(tmp_path):
    """Two programs that differ only in a named scope get two entries of
    the persistent compilation cache (the package puts the metadata in
    the key): the second is not served the first's ``op_name``s."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}

    def make(scope):
        def step(x):
            with jax.named_scope(scope):
                return x * 2 + 1
        return step

    x = np.ones(8, np.float32)
    try:
        for k, v in zip(keys, (str(tmp_path), 0, 0)):
            jax.config.update(k, v)
        cc.reset_cache()
        first = jax.jit(make("alpha")).lower(x).compile().as_text()
        second = jax.jit(make("beta")).lower(x).compile().as_text()
        assert os.listdir(tmp_path)  # the cache was written
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert "/alpha/" in first
    assert "/beta/" in second and "/alpha/" not in second
