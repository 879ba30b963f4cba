"""Engine-side traffic integration: the mesh-sharded coded head (one code
block per device via shard_map) and the scheduler-driven ServeEngine
(DESIGN.md §10)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import require_devices
from repro.configs import get_config
from repro.models.registry import build_model

N_BLOCKS = 16  # the serving head's block count (models.config.coded_blocks)


@pytest.fixture(scope="module")
def coded_model():
    cfg = get_config("phi3-mini-3.8b", smoke=True).scaled(coded=True, coded_parity=2)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _mesh(size: int = N_BLOCKS):
    """A 1-D ``model`` mesh of ``size`` devices: one code block per device
    at the default, several (N_BLOCKS / size) at a divisor of the count."""
    from jax.sharding import Mesh

    require_devices(size)
    return Mesh(np.array(jax.devices()[:size]), ("model",))


# --------------------------------------------------------------------------
# the sharded head primitive
# --------------------------------------------------------------------------
def test_coded_head_matvec_sharded_matches_single_device():
    """shard_map head == CodedLinear head on identical masks, across every
    single- and double-erasure pattern the 2-parity head can decode."""
    require_devices(N_BLOCKS)
    from repro.core.coded_ops import CodedLinear
    from repro.kernels.ops import coded_head_matvec

    n_data, n_parity = N_BLOCKS - 2, 2
    rng = np.random.default_rng(0)
    w = rng.standard_normal((220, 32)).astype(np.float32)
    cl = CodedLinear(n_data=n_data, n_parity=n_parity, out_features=220)
    wc = cl.encode(jnp.asarray(w))
    x = jnp.asarray(rng.standard_normal((32, 3)).astype(np.float32))
    mesh = _mesh()
    masks = [np.ones(N_BLOCKS)]
    for i in range(0, N_BLOCKS, 5):
        m = np.ones(N_BLOCKS)
        m[i] = 0.0
        masks.append(m)
        m2 = m.copy()
        m2[(i + 7) % N_BLOCKS] = 0.0
        masks.append(m2)
    for m in masks:
        mj = jnp.asarray(m, jnp.float32)
        ref = np.asarray(cl.apply(wc, x, mj))
        full = np.asarray(coded_head_matvec(wc, x, mj, n_data, n_parity, mesh=mesh))
        got = full[:220]
        np.testing.assert_allclose(got, ref[:220], rtol=0, atol=1e-5)
        # and both recover the true product
        exact = w @ np.asarray(x)
        assert np.abs(got - exact).max() / np.abs(exact).max() < 1e-3


@pytest.mark.parametrize("size", [2, 4, 8])
def test_coded_head_matvec_several_blocks_per_device(size):
    """A mesh axis that divides the block count holds N_BLOCKS / size whole
    blocks per device; erasure stays per block, and the result matches the
    single-program head on every mask."""
    from repro.core.coded_ops import CodedLinear
    from repro.kernels.ops import coded_head_matvec

    n_data, n_parity = N_BLOCKS - 2, 2
    rng = np.random.default_rng(size)
    w = rng.standard_normal((220, 32)).astype(np.float32)
    cl = CodedLinear(n_data=n_data, n_parity=n_parity, out_features=220)
    wc = cl.encode(jnp.asarray(w))
    x = jnp.asarray(rng.standard_normal((32, 3)).astype(np.float32))
    mesh = _mesh(size)
    for erased in [(), (0,), (1, 2), (N_BLOCKS - 1, 5)]:
        m = np.ones(N_BLOCKS)
        m[list(erased)] = 0.0
        mj = jnp.asarray(m, jnp.float32)
        ref = np.asarray(cl.apply(wc, x, mj))[:220]
        got = np.asarray(
            coded_head_matvec(wc, x, mj, n_data, n_parity, mesh=mesh)
        )[:220]
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-5)


def test_validate_coded_head_mesh_rejects_wrong_geometry():
    require_devices(2)
    from jax.sharding import Mesh
    from repro.sharding.policy import validate_coded_head_mesh

    require_devices(3)
    mesh = Mesh(np.array(jax.devices()[:3]), ("model",))
    with pytest.raises(ValueError):  # 3 devices cannot hold 16 whole blocks
        validate_coded_head_mesh(mesh, N_BLOCKS, "model")
    with pytest.raises(ValueError):
        validate_coded_head_mesh(mesh, 3, "data")
    for size in (1, 2, 4, 8, N_BLOCKS):  # whole blocks on every device
        mesh = Mesh(np.array(jax.devices()[:size]), ("model",))
        validate_coded_head_mesh(mesh, N_BLOCKS, "model")


# --------------------------------------------------------------------------
# the engine on a mesh
# --------------------------------------------------------------------------
def test_engine_mesh_sharded_head_bit_identical(coded_model):
    """ISSUE 5 acceptance: the mesh-sharded engine (one code block per
    device, erasure = dropping a device's output) produces bit-identical
    tokens to the single-device engine on identical masks."""
    require_devices(N_BLOCKS)
    from repro.serve import Request, ServeEngine

    cfg, model, params = coded_model
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, 8).astype(np.int32) for _ in range(4)]
    masks = [np.ones(N_BLOCKS), np.ones(N_BLOCKS)]
    masks[1][3] = 0.0
    masks[1][9] = 0.0
    state = {"n": 0}

    def mask_fn():
        state["n"] += 1
        return masks[state["n"] % 2]

    def run(mesh):
        state["n"] = 0
        eng = ServeEngine(
            model, params, n_slots=2, s_max=32, mask_fn=mask_fn, mesh=mesh
        )
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p.copy(), max_new_tokens=8))
        return {r.uid: r.out_tokens for r in eng.run()}

    ref = run(None)
    got = run(_mesh())
    assert ref == got


def test_engine_mesh_places_state_on_the_mesh(coded_model):
    """On a 4-device head axis (4 blocks per device) the engine emits the
    single-device tokens, and every array it steps with lives on the mesh:
    the coded head split by blocks, everything else replicated — none left
    on the default device alone."""
    from jax.sharding import NamedSharding

    from repro.serve import Request, ServeEngine

    cfg, model, params = coded_model
    mesh = _mesh(4)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, 8).astype(np.int32) for _ in range(3)]
    mask = np.ones(N_BLOCKS)
    mask[[4, 13]] = 0.0  # two blocks on two different devices

    def run(mesh):
        eng = ServeEngine(model, params, n_slots=2, s_max=32,
                          mask_fn=lambda: mask, mesh=mesh)
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p.copy(), max_new_tokens=6))
        return eng, {r.uid: r.out_tokens for r in eng.run()}

    _, ref = run(None)
    eng, got = run(mesh)
    assert ref == got
    devices = set(mesh.devices.flat)
    head = eng.params["lm_head_coded"]
    assert head.sharding.spec[0] == "model"
    assert {s.data.shape[0] for s in head.addressable_shards} == {
        head.shape[0] // 4
    }
    for leaf in jax.tree.leaves((eng.params, eng.cache, eng._last_tok)):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.device_set == devices


def test_engine_mesh_requires_coded_config(coded_model):
    require_devices(N_BLOCKS)
    from repro.serve import ServeEngine

    cfg, _, _ = coded_model
    plain = get_config("phi3-mini-3.8b", smoke=True)
    model = build_model(plain)
    params = model.init(jax.random.key(0))
    with pytest.raises(ValueError):
        ServeEngine(model, params, n_slots=1, s_max=32, mesh=_mesh())


# --------------------------------------------------------------------------
# scheduler-driven engine (fake model-time clock)
# --------------------------------------------------------------------------
class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def _drive(eng, sched, clock, dt=0.5, max_steps=500):
    for _ in range(max_steps):
        if sched.finished:
            break
        busy = eng.step()
        if busy:
            clock.now += dt
        else:
            nxt = sched.next_arrival()
            if nxt is None:
                break
            clock.now = max(clock.now, nxt)
    assert sched.finished


def test_engine_with_scheduler_records_completions(coded_model):
    from repro.serve import Request, ServeEngine, TraceScheduler, replay_trace

    cfg, model, params = coded_model
    rng = np.random.default_rng(1)
    t_arrival = np.array([0.0, 0.0, 2.0, 10.0])
    n_tokens = np.array([4, 6, 4, 3])
    trace = replay_trace(
        t_arrival, n_tokens, t_token=0.5, slo_factor=8.0, queue_grace=20.0
    )
    payloads = [
        Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab, 6).astype(np.int32),
            max_new_tokens=int(n_tokens[i]),
        )
        for i in range(len(n_tokens))
    ]
    sched = TraceScheduler(trace, 2, t_step_init=0.5, payloads=payloads)
    clock = FakeClock()
    eng = ServeEngine(model, params, n_slots=2, s_max=32, scheduler=sched, clock=clock)
    _drive(eng, sched, clock)
    res = sched.results()
    assert np.isfinite(res["t_complete"]).all()
    assert res["slo_met"].all()
    assert not res["rejected"].any()
    # every engine-side request generated exactly its token budget
    assert sorted(len(r.out_tokens) for r in eng.completed) == sorted(n_tokens)
    # deadlines/sched indices were attached to the payloads
    assert all(
        r.sched_idx is not None and r.deadline is not None for r in eng.completed
    )


def test_engine_scheduler_one_token_request_completes_at_prefill(coded_model):
    """A 1-token request is DONE after its prefill token; the engine must
    free the slot immediately instead of decoding past the budget (the
    launcher-crash regression: scheduler KeyError on the extra token)."""
    from repro.serve import Request, ServeEngine, TraceScheduler, replay_trace

    cfg, model, params = coded_model
    rng = np.random.default_rng(4)
    n_tokens = np.array([1, 3, 1])
    trace = replay_trace(np.zeros(3), n_tokens, t_token=0.5, slo_factor=8.0)
    payloads = [
        Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab, 4).astype(np.int32),
            max_new_tokens=int(n_tokens[i]),
        )
        for i in range(3)
    ]
    sched = TraceScheduler(trace, 2, t_step_init=0.5, payloads=payloads)
    clock = FakeClock()
    eng = ServeEngine(model, params, n_slots=2, s_max=32, scheduler=sched, clock=clock)
    _drive(eng, sched, clock)
    assert sorted(len(r.out_tokens) for r in eng.completed) == [1, 1, 3]
    assert np.isfinite(sched.results()["t_complete"]).all()


def test_engine_deadline_parity_tokens_exact_under_straggling(coded_model):
    """The deadline-aware engine (scheduler + DeadlineAwareParity + shard
    latencies) produces the SAME tokens as a healthy engine — masks change
    per step, logits never do (the coded guarantee), and the scheduler
    bookkeeping rides on top."""
    from repro.core.adaptive import DeadlineAwareParity, ParityController
    from repro.serve import Request, ServeEngine, TraceScheduler, replay_trace

    cfg, model, params = coded_model
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, 6).astype(np.int32) for _ in range(3)]
    n_tokens = np.array([5, 5, 5])
    trace = replay_trace(
        np.zeros(3), n_tokens, t_token=0.5, slo_factor=8.0, queue_grace=20.0
    )

    lat_state = np.random.default_rng(3)

    def latency_fn():
        lat = 1e-3 * (1.0 + 0.1 * lat_state.random(N_BLOCKS))
        lat[lat_state.random(N_BLOCKS) < 0.3] *= 50.0
        return lat

    def run(straggle: bool):
        payloads = [
            Request(uid=i, prompt=p.copy(), max_new_tokens=5)
            for i, p in enumerate(prompts)
        ]
        sched = TraceScheduler(trace, 3, t_step_init=0.5, payloads=payloads)
        clock = FakeClock()
        ctrl = ParityController(N_BLOCKS)
        eng = ServeEngine(
            model,
            params,
            n_slots=3,
            s_max=32,
            latency_fn=latency_fn if straggle else None,
            parity_policy=DeadlineAwareParity(ctrl) if straggle else None,
            scheduler=sched,
            clock=clock,
        )
        _drive(eng, sched, clock)
        return {r.uid: r.out_tokens for r in eng.completed}

    assert run(False) == run(True)


def test_engine_observes_through_parity_policy(coded_model):
    """The engine must feed latency observations THROUGH the deadline
    policy (calm/onset/spike economics), not the bare controller — a
    controller-only observe freezes the policy at its pessimistic priors
    (the code-review regression: live engine stuck at fixed-parity)."""
    from repro.core.adaptive import DeadlineAwareParity, ParityController
    from repro.serve import Request, ServeEngine

    cfg, model, params = coded_model
    policy = DeadlineAwareParity(
        ParityController(N_BLOCKS), onset_prior=1e-4, spike_prior=2.0
    )
    eng = ServeEngine(
        model,
        params,
        n_slots=1,
        s_max=32,
        latency_fn=lambda: np.full(N_BLOCKS, 1e-3),
        parity_policy=policy,
    )
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab, 6).astype(np.int32)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=policy.calm_patience + 4))
    assert not policy.calm
    eng.run()
    assert policy.calm  # healthy steps advanced the policy's calm window


def test_engine_parity_policy_controller_consistency(coded_model):
    from repro.core.adaptive import DeadlineAwareParity, ParityController
    from repro.serve import ServeEngine

    cfg, model, params = coded_model
    policy = DeadlineAwareParity(ParityController(N_BLOCKS))
    other = ParityController(N_BLOCKS)
    with pytest.raises(ValueError):
        ServeEngine(
            model,
            params,
            n_slots=1,
            s_max=32,
            parity_controller=other,
            parity_policy=policy,
        )
    eng = ServeEngine(model, params, n_slots=1, s_max=32, parity_policy=policy)
    assert eng.parity_controller is policy.controller
