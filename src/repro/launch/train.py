"""End-to-end training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --smoke \\
        --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ck --ckpt-every 20

Runs the full production stack on whatever devices exist (the CPU container
runs reduced/smoke configs on a 1x1 mesh; a TPU pod runs the real configs on
the production mesh): data pipeline -> pjit'd train step (microbatching,
remat, optional coded gradient aggregation) -> AdamW (int8 moments
optional) -> atomic checkpoints with restart, health-monitor hooks.

Coded mode (DESIGN.md §12) adds the full straggler-robust path:

  * per-step masks from a two-state Markov straggler stream
    (``cluster.straggler.MarkovStragglerPolicy`` — the serve bench's
    injection, per training step): with replication s the master waits for
    the first m−s coded messages, so the mask drops the s realized-slowest
    workers;
  * ``--adaptive-s``: the replication level is re-chosen online per step by
    ``core.adaptive.ReplicationController`` from the observed per-worker
    latencies (cost-model argmin; jit-compiled steps are cached per level);
  * ``--compress int8``: error-feedback int8 quantization of the coded
    messages (``optim.compression``), residuals carried in state["err"];
  * ``--kill-at N``: device-death drill — the last DP slice dies at step N,
    its workers' messages stop arriving (unrecoverable masks are *skipped*,
    params untouched), and after ``--detect-steps`` consecutive skips the
    elastic protocol runs: ``shrink_mesh`` -> ``restore_checkpoint`` with
    the survivor mesh's shardings -> training resumes.

``--dry-run`` prints the fully-resolved training configuration (model,
mesh, optimizer, microbatching/gradient-coding plan) and exits before any
compilation or training step — the config-validation idiom.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding

from repro.cluster.straggler import MarkovStragglerPolicy
from repro.configs import get_config
from repro.core.adaptive import ReplicationController
from repro.data import make_pipeline
from repro.models.registry import build_model
from repro.optim import AdamWConfig, warmup_cosine
from repro.runtime import (
    gc_checkpoints,
    latest_step,
    restore_checkpoint,
    save_checkpoint,
    wait_for_saves,
)
from repro.runtime.elastic import shrink_mesh
from repro.runtime.health import HealthMonitor
from repro.sharding.ctx import sharding_hints
from repro.sharding.policy import make_policy
from repro.train.loop import TrainConfig, init_train_state, make_train_step


def make_local_mesh(model: int | None = None):
    n = len(jax.devices())
    if model is None:
        model = 1
        while model * 2 <= n and n % (model * 2) == 0 and model < 16:
            model *= 2
    elif n % model != 0:
        raise ValueError(f"--mesh-model {model} does not divide {n} devices")
    data = n // model
    # Auto axes: the train step places state by NamedSharding and lets GSPMD
    # propagate, which Explicit axes (jax.make_mesh's default) refuse
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def _allowed_levels(kind: str, m: int, s_max: int) -> list[int]:
    """Replication levels the adaptive controller may pick from."""
    if kind == "frc":
        return [s for s in range(0, min(s_max, m - 1) + 1) if m % (s + 1) == 0]
    return list(range(0, min(s_max, m - 1) + 1))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="End-to-end LM training on the production stack",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    ap.add_argument("--arch", default="glm4-9b",
                    help="model architecture id (see repro.configs.ARCHS)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced model config sized for the CPU container")
    ap.add_argument("--steps", type=int, default=100,
                    help="training steps to run")
    ap.add_argument("--batch", type=int, default=8,
                    help="global batch size (sequences per step)")
    ap.add_argument("--seq", type=int, default=128,
                    help="sequence length in tokens")
    ap.add_argument("--lr", type=float, default=3e-3,
                    help="peak learning rate (warmup-cosine schedule)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation microbatches per step "
                         "(= coded workers in gradient-coding mode)")
    ap.add_argument("--mesh-model", type=int, default=None,
                    help="TP width of the local mesh (default: widest that "
                         "fits; set small to leave DP slices for the drill)")
    ap.add_argument("--moment-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8"],
                    help="AdamW moment storage dtype (int8 halves optimizer HBM)")
    ap.add_argument("--gradient-coding", default=None, choices=[None, "frc", "cyclic"],
                    help="coded gradient aggregation scheme across microbatches")
    ap.add_argument("--gc-stragglers", type=int, default=1,
                    help="straggler budget s (maximum level when --adaptive-s)")
    ap.add_argument("--adaptive-s", action="store_true",
                    help="re-choose the replication level online from the "
                         "ReplicationController's latency posterior")
    ap.add_argument("--compress", default=None, choices=[None, "int8"],
                    help="error-feedback compression of the coded messages")
    ap.add_argument("--straggler-prob", type=float, default=0.0,
                    help="stationary straggler fraction of the Markov "
                         "injection (paper §5.3.1 uses 0.2)")
    ap.add_argument("--straggler-slowdown", type=float, default=3.0,
                    help="compute-time multiplier while slow (paper: 3x)")
    ap.add_argument("--straggler-persistence", type=float, default=25.0,
                    help="mean steps a slow regime lasts")
    ap.add_argument("--straggler-onset", type=float, default=None,
                    help="per-step onset probability (overrides --straggler-prob)")
    ap.add_argument("--kill-at", type=int, default=None,
                    help="device-death drill: the last DP slice dies at this "
                         "step; elastic shrink/restore resumes training")
    ap.add_argument("--detect-steps", type=int, default=2,
                    help="consecutive unrecoverable steps before the death "
                         "drill declares the slice dead and re-meshes")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (None disables checkpointing)")
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="save an (async, atomic) checkpoint every N steps")
    ap.add_argument("--log-every", type=int, default=10,
                    help="print loss/throughput every N steps")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed (init, data pipeline, straggler draws)")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the resolved config and exit without executing")
    args = ap.parse_args(argv)

    if args.kill_at is not None and not args.ckpt_dir:
        ap.error("--kill-at needs --ckpt-dir (restore-with-resharding)")
    if args.kill_at is not None and not args.gradient_coding:
        ap.error("--kill-at needs --gradient-coding (masks detect the death)")

    cfg = get_config(args.arch, smoke=args.smoke)
    m = args.microbatches
    if args.dry_run:
        n_params, n_act = cfg.param_count()
        print("[train] --dry-run resolved config:")
        print(f"  arch={cfg.name} family={cfg.family} smoke={args.smoke} "
              f"params~{n_params:,.0f} (active~{n_act:,.0f})")
        print(f"  devices={len(jax.devices())} steps={args.steps} "
              f"batch={args.batch} seq={args.seq} lr={args.lr}")
        print(f"  microbatches={m} moment_dtype={args.moment_dtype} "
              f"gradient_coding={args.gradient_coding} "
              f"gc_stragglers={args.gc_stragglers} adaptive_s={args.adaptive_s} "
              f"compress={args.compress}")
        print(f"  straggler: prob={args.straggler_prob} "
              f"slowdown={args.straggler_slowdown} "
              f"persistence={args.straggler_persistence} "
              f"onset={args.straggler_onset}")
        print(f"  ckpt_dir={args.ckpt_dir} ckpt_every={args.ckpt_every} "
              f"kill_at={args.kill_at}")
        return
    model = build_model(cfg)
    mesh = make_local_mesh(args.mesh_model)
    print(f"[train] arch={cfg.name} (smoke={args.smoke}) mesh={dict(mesh.shape)} "
          f"params~{model and sum(np.prod(s.shape) for s in jax.tree.leaves(model.param_shapes())):,}")

    opt_cfg = AdamWConfig(
        lr=warmup_cosine(args.lr, max(args.steps // 20, 1), args.steps),
        moment_dtype=args.moment_dtype,
    )

    def train_cfg(s: int) -> TrainConfig:
        return TrainConfig(
            microbatches=m,
            gradient_coding=args.gradient_coding,
            gc_stragglers=s,
            compression=args.compress,
        )

    tc0 = train_cfg(args.gc_stragglers)
    state_sds = jax.eval_shape(
        lambda k: init_train_state(model, k, opt_cfg, tc0), jax.random.key(args.seed)
    )

    # --- mesh-dependent pieces, rebuilt by the elastic protocol ------------
    jit_cache: dict[int, object] = {}
    policy = state_sh = None

    def install_mesh(new_mesh):
        nonlocal mesh, policy, state_sh
        mesh = new_mesh
        policy = make_policy(mesh, cfg)
        state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                policy.state_specs(state_sds))
        jit_cache.clear()

    def jit_step(s: int):
        if s not in jit_cache:
            step_fn = make_train_step(model, opt_cfg, train_cfg(s))
            jit_cache[s] = jax.jit(
                step_fn, in_shardings=(state_sh, None, None),
                out_shardings=(state_sh, None), donate_argnums=(0,),
            )
        return jit_cache[s]

    install_mesh(mesh)

    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        start, state = restore_checkpoint(args.ckpt_dir, state_sds,
                                          shardings=state_sh)
        print(f"[train] resumed from step {start}")
    else:
        with mesh:
            state = jax.jit(
                lambda k: init_train_state(model, k, opt_cfg, tc0),
                out_shardings=state_sh,
            )(jax.random.key(args.seed))

    # --- straggler injection + online replication control ------------------
    stream = None
    if args.gradient_coding and (args.straggler_prob > 0 or args.straggler_onset):
        if args.straggler_onset is not None:
            pol = MarkovStragglerPolicy(
                onset=args.straggler_onset, slow_factor=args.straggler_slowdown,
                persistence=args.straggler_persistence)
        else:
            pol = MarkovStragglerPolicy.from_stationary(
                args.straggler_prob, slow_factor=args.straggler_slowdown,
                persistence=args.straggler_persistence)
        stream = pol.stream(m, seed=args.seed)
    controller = ReplicationController(m) if args.adaptive_s else None
    levels = _allowed_levels(args.gradient_coding or "cyclic", m,
                             args.gc_stragglers)
    s_cur = args.gc_stragglers if args.gradient_coding else 0

    pipe = make_pipeline(cfg, seq=args.seq, global_batch=args.batch, seed=args.seed)
    health = HealthMonitor(n_workers=max(m, 1))
    dead_ranks: set[int] = set()
    consec_bad = 0
    skipped = 0
    t0 = time.time()
    tokens_done = 0
    step = start
    while step < args.steps:
        with mesh, sharding_hints(policy.hints()):
            batch = jax.tree.map(jnp.asarray, pipe.batch(step))
            mask = None
            if args.gradient_coding:
                if controller is not None:
                    s_cur = controller.replication(levels)
                mult = stream.step() if stream is not None else np.ones(m)
                if dead_ranks:
                    dp = mesh.shape.get("data", 1)
                    dead_w = [w for w in range(m) if (w % dp) in dead_ranks]
                    mult = mult.copy()
                    mult[dead_w] = np.inf
                # master waits for the first m - s messages: drop the s
                # realized-slowest (dead workers never arrive at all)
                alive = np.isfinite(mult)
                keep = np.zeros(m, bool)
                order = np.argsort(mult)
                keep[order[: max(m - s_cur, 1)]] = True
                keep &= alive
                mask = jnp.asarray(keep, jnp.float32)
                if controller is not None:
                    controller.observe(np.where(alive, mult, np.inf))
            ts = time.time()
            state, metrics = jit_step(s_cur)(state, batch, mask) \
                if args.gradient_coding else jit_step(0)(state, batch)
            health.record(0, rows=args.batch * args.seq,
                          seconds=max(time.time() - ts, 1e-9))
            ok = float(metrics.get("ok", 1.0))
            if ok < 0.5:
                skipped += 1
                consec_bad += 1
            else:
                consec_bad = 0
                tokens_done += args.batch * args.seq
            if (step + 1) % args.log_every == 0 or step == start:
                print(f"[train] step {step+1:5d} loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} s={s_cur} "
                      f"ok={ok:.0f} tok/s={tokens_done / (time.time() - t0):,.0f}")
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0 and ok >= 0.5:
                save_checkpoint(args.ckpt_dir, step + 1, state, blocking=False)
                gc_checkpoints(args.ckpt_dir, keep=3)

        # --- device-death drill + elastic recovery ------------------------
        if args.kill_at is not None and step + 1 == args.kill_at:
            dp = mesh.shape.get("data", 1)
            if dp > 1:
                dead_ranks.add(dp - 1)
                print(f"[train] drill: DP slice {dp - 1} died at step {step + 1}")
            else:
                print("[train] drill skipped: mesh has a single DP slice")
        if dead_ranks and consec_bad >= args.detect_steps:
            print(f"[train] {consec_bad} unrecoverable steps -> elastic recovery")
            wait_for_saves()
            dp = mesh.shape.get("data", 1)
            dead_dev = {d.id for i, row in enumerate(mesh.devices)
                        for d in np.asarray(row).flat if i in dead_ranks} \
                if mesh.devices.ndim > 1 else set()
            new_mesh = shrink_mesh(mesh, dead_dev)
            install_mesh(new_mesh)
            ck_step, state = restore_checkpoint(args.ckpt_dir, state_sds,
                                                shardings=state_sh)
            print(f"[train] re-meshed {dp}->{new_mesh.shape.get('data', 1)} DP "
                  f"slices; resumed from checkpoint step {ck_step}")
            dead_ranks.clear()
            consec_bad = 0
            step = ck_step
            continue
        step += 1
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, state)
        wait_for_saves()
    print(f"[train] done in {time.time() - t0:.1f}s; skipped={skipped}; "
          f"final loss={float(metrics['loss']):.4f}")


if __name__ == "__main__":
    main()
