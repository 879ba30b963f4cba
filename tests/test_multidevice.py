"""Multi-device SPMD integration (subprocess with forced host devices —
the main test process must keep its single real device)."""
import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, n_devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_shard_map_coded_block_matmul():
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.core.coded_ops import coded_block_matmul, CodedLinear
        mesh = jax.make_mesh((8,), ("model",), axis_types=(AxisType.Auto,))
        cl = CodedLinear(n_data=6, n_parity=2, out_features=48)
        rng = np.random.default_rng(0)
        w = rng.standard_normal((48, 32)).astype(np.float32)
        wc = cl.encode(jnp.asarray(w))
        x = rng.standard_normal((32, 4)).astype(np.float32)
        mask = np.ones(8); mask[3] = 0; mask[6] = 0
        y = coded_block_matmul(mesh, "model", wc, jnp.asarray(x),
                               jnp.asarray(mask, jnp.float32), 6, 2)
        err = np.abs(np.asarray(y)[:48] - w @ x).max() / np.abs(w @ x).max()
        assert err < 1e-3, err
        print("OK", err)
    """)
    assert "OK" in out


def test_pjit_train_step_on_mesh():
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding
        from repro.configs import get_config
        from repro.models.registry import build_model
        from repro.optim import AdamWConfig
        from repro.sharding.ctx import sharding_hints
        from repro.sharding.policy import make_policy
        from repro.train.loop import TrainConfig, init_train_state, make_train_step
        from repro.data import make_pipeline

        cfg = get_config("glm4-9b", smoke=True)
        model = build_model(cfg)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        policy = make_policy(mesh, cfg)
        opt = AdamWConfig(lr=1e-3, moment_dtype="int8")
        state_sds = jax.eval_shape(lambda k: init_train_state(model, k, opt),
                                   jax.random.key(0))
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          policy.state_specs(state_sds))
        step = jax.jit(make_train_step(model, opt, TrainConfig(microbatches=2)),
                       in_shardings=(sh, None, None), out_shardings=(sh, None),
                       donate_argnums=(0,))
        pipe = make_pipeline(cfg, seq=32, global_batch=8)
        with mesh, sharding_hints(policy.hints()):
            state = jax.jit(lambda k: init_train_state(model, k, opt),
                            out_shardings=sh)(jax.random.key(0))
            for i in range(3):
                batch = jax.tree.map(jnp.asarray, pipe.batch(i))
                state, m = step(state, batch, None)
        loss = float(m["loss"])
        assert np.isfinite(loss)
        print("OK", loss)
    """)
    assert "OK" in out


def test_sharded_equals_single_device():
    """The pjit'd step on a 2x2 mesh reproduces the single-device update."""
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding
        from repro.configs import get_config
        from repro.models.registry import build_model
        from repro.optim import AdamWConfig
        from repro.sharding.ctx import sharding_hints
        from repro.sharding.policy import make_policy
        from repro.train.loop import TrainConfig, init_train_state, make_train_step
        from repro.data import make_pipeline

        cfg = get_config("phi3-mini-3.8b", smoke=True)
        model = build_model(cfg)
        opt = AdamWConfig(lr=1e-3)
        pipe = make_pipeline(cfg, seq=16, global_batch=4)
        batch = jax.tree.map(jnp.asarray, pipe.batch(0))
        step_fn = make_train_step(model, opt, TrainConfig())

        # single device
        s0 = init_train_state(model, jax.random.key(0), opt)
        s1, _ = jax.jit(step_fn)(s0, batch)

        # 2x2 mesh
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        policy = make_policy(mesh, cfg)
        sds = jax.eval_shape(lambda k: init_train_state(model, k, opt),
                             jax.random.key(0))
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), policy.state_specs(sds))
        with mesh, sharding_hints(policy.hints()):
            sm = jax.jit(lambda k: init_train_state(model, k, opt),
                         out_shardings=sh)(jax.random.key(0))
            sm1, _ = jax.jit(step_fn, in_shardings=(sh, None),
                             out_shardings=(sh, None))(sm, batch)
        worst = 0.0
        for a, b in zip(jax.tree.leaves(s1["params"]), jax.tree.leaves(sm1["params"])):
            worst = max(worst, float(np.abs(np.asarray(a, np.float32)
                                            - np.asarray(b, np.float32)).max()))
        assert worst < 5e-3, worst
        print("OK", worst)
    """)
    assert "OK" in out


def test_elastic_shrink_and_resume():
    """8-device job checkpoints; 4 survivors restore with resharding."""
    out = run_py("""
        import tempfile, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding
        from repro.configs import get_config
        from repro.models.registry import build_model
        from repro.optim import AdamWConfig
        from repro.runtime import restore_checkpoint, save_checkpoint
        from repro.runtime.elastic import make_mesh_from_devices, plan_mesh_shape
        from repro.sharding.policy import make_policy
        from repro.train.loop import TrainConfig, init_train_state, make_train_step
        from repro.data import make_pipeline

        cfg = get_config("glm4-9b", smoke=True)
        model = build_model(cfg)
        opt = AdamWConfig(lr=1e-3)
        step_fn = make_train_step(model, opt, TrainConfig())
        pipe = make_pipeline(cfg, seq=16, global_batch=8)
        devs = jax.devices()

        mesh8 = make_mesh_from_devices(devs, *plan_mesh_shape(8, model=2))
        pol8 = make_policy(mesh8, cfg)
        sds = jax.eval_shape(lambda k: init_train_state(model, k, opt),
                             jax.random.key(0))
        sh8 = jax.tree.map(lambda s: NamedSharding(mesh8, s), pol8.state_specs(sds))
        with mesh8:
            st = jax.jit(lambda k: init_train_state(model, k, opt),
                         out_shardings=sh8)(jax.random.key(0))
            st, _ = jax.jit(step_fn, in_shardings=(sh8, None),
                            out_shardings=(sh8, None))(st, jax.tree.map(jnp.asarray, pipe.batch(0)))
        d = tempfile.mkdtemp()
        save_checkpoint(d, 1, st)

        # "4 hosts died": rebuild on 4 devices, restore with resharding
        mesh4 = make_mesh_from_devices(devs[:4], *plan_mesh_shape(4, model=2))
        pol4 = make_policy(mesh4, cfg)
        sh4 = jax.tree.map(lambda s: NamedSharding(mesh4, s), pol4.state_specs(sds))
        step_r, st2 = restore_checkpoint(d, sds, shardings=sh4)
        with mesh4:
            st2, m = jax.jit(step_fn, in_shardings=(sh4, None),
                             out_shardings=(sh4, None))(st2, jax.tree.map(jnp.asarray, pipe.batch(1)))
        assert np.isfinite(float(m["loss"]))
        print("OK", step_r, float(m["loss"]))
    """)
    assert "OK" in out


def test_dryrun_cell_subprocess():
    """One real dry-run cell end-to-end (fast arch) on the 512-dev mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "mamba2-130m",
         "--shape", "decode_32k", "--multi-pod", "both"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert out.stdout.count("OK") == 2  # single-pod AND multi-pod


def test_dryrun_pins_the_cpu_platform():
    """The dry-run compiles for placeholder CPU devices: importing it pins
    JAX to the CPU whatever the caller's environment says, so it never
    reaches for an accelerator its parent may hold."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import os
            import repro.launch.dryrun
            import jax
            print(os.environ["JAX_PLATFORMS"], jax.default_backend(),
                  len(jax.devices()))
        """)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["cpu", "cpu", "512"]


def test_compile_cache_location():
    """$JAX_COMPILATION_CACHE_DIR when set, else .jax_cache/ in the checkout
    — a fixed path, so one run's compiles serve the next."""
    code = "import repro, jax; print(jax.config.jax_compilation_cache_dir)"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    got = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env, cwd="/")
    assert got.returncode == 0, got.stderr[-2000:]
    assert got.stdout.strip() == os.path.join(REPO, ".jax_cache")
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(REPO, ".jax_cache_env")
    got = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env, cwd="/")
    assert got.returncode == 0, got.stderr[-2000:]
    assert got.stdout.strip() == env["JAX_COMPILATION_CACHE_DIR"]

