"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both \\
        --out reports/dryrun.json

This is the proof that the distribution config is coherent without real
hardware: ``.lower().compile()`` must succeed for the 16x16 (256-chip) pod
mesh AND the 2x16x16 (512-chip) multi-pod mesh for every cell, and the
compiled artifact yields the memory/cost/collective numbers the roofline
analysis (EXPERIMENTS.md §Roofline) reads.
"""
# The dry-run (and ONLY the dry-run) needs 512 placeholder devices; jax
# locks the device count and the platform on first init, so this MUST
# precede every import.  The platform is pinned to the CPU: these are
# placeholder devices by design, and on a machine with an accelerator the
# process that launched the dry-run may already hold it.  Inherited force
# flags are stripped first: XLA keeps the LAST duplicate flag, and callers
# (e.g. a pytest parent whose conftest forces 16 devices for the shard_map
# serving tests) would otherwise silently override the 512 this launcher
# requires.
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + " ".join(
        t
        for t in os.environ.get("XLA_FLAGS", "").split()
        if not t.startswith("--xla_force_host_platform_device_count")
    )
).strip()

import argparse    # noqa: E402
import json        # noqa: E402
import time        # noqa: E402
import traceback   # noqa: E402

import jax                    # noqa: E402
import jax.numpy as jnp       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, SHAPES, applicable, get_config  # noqa: E402
from repro.configs.shapes import Workload  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.sharding.ctx import sharding_hints  # noqa: E402
from repro.sharding.policy import make_policy  # noqa: E402
from repro.train.loop import TrainConfig, make_train_step  # noqa: E402
from repro.utils.hlo import analyze_hlo, roofline  # noqa: E402

SDS = jax.ShapeDtypeStruct


# --------------------------------------------------------------------------
# per-arch training plan (what a launcher config file would pin)
# --------------------------------------------------------------------------
def train_plan(cfg: ModelConfig) -> dict:
    n, _ = cfg.param_count()
    if n >= 50e9:
        # int8 moments + per-sequence microbatches + sequence-sharded
        # activations: required to fit 16 GB/chip (DESIGN.md §5)
        return {"moment_dtype": "int8", "microbatches": 16, "seq_shard_act": True}
    if n >= 8e9:
        return {"moment_dtype": "float32", "microbatches": 4, "seq_shard_act": False}
    return {"moment_dtype": "float32", "microbatches": 1, "seq_shard_act": False}


# --------------------------------------------------------------------------
# analytic useful-FLOPs (global): 6·N·D train / 2·N·D forward (+ attn reads)
# --------------------------------------------------------------------------
def model_flops(cfg: ModelConfig, wl: Workload) -> float:
    _, n_act = cfg.param_count()
    t = wl.batch * wl.seq
    hd = cfg.resolved_head_dim
    if wl.kind == "train":
        attn = 12 * cfg.n_layers * wl.batch * wl.seq**2 * cfg.n_heads * hd
        return 6.0 * n_act * t + (attn if cfg.n_heads else 0)
    if wl.kind == "prefill":
        attn = 4 * cfg.n_layers * wl.batch * wl.seq**2 * cfg.n_heads * hd
        return 2.0 * n_act * t + (attn if cfg.n_heads else 0)
    # decode: one token per sequence + KV attention over the cache
    attn = 4 * cfg.n_layers * wl.batch * wl.seq * cfg.n_heads * hd
    if cfg.family == "hybrid":
        n_apps = cfg.n_layers // cfg.attn_every
        attn = 4 * n_apps * wl.batch * wl.seq * cfg.n_heads * hd
    if cfg.family == "ssm":
        attn = 0
    return 2.0 * n_act * wl.batch + attn


# --------------------------------------------------------------------------
# cell construction
# --------------------------------------------------------------------------
_PLAN_KEYS = {"microbatches", "moment_dtype", "seq_shard_act", "shard_grad_accum"}


def build_cell(cfg: ModelConfig, wl: Workload, mesh, *, coded: bool = False,
               overrides: dict | None = None):
    """Returns (jitted_fn, example_args (SDS), meta).

    ``overrides``: perf-iteration knobs — ModelConfig fields (onehot_ce,
    pad_heads, moe_dispatch_groups, aligned_decode, param_dtype, ...) or
    train-plan fields (microbatches, moment_dtype, seq_shard_act).
    """
    if coded:
        cfg = cfg.scaled(coded=True)
    plan_over = {}
    if overrides:
        cfg_over = {k: v for k, v in overrides.items() if k not in _PLAN_KEYS}
        plan_over = {k: v for k, v in overrides.items() if k in _PLAN_KEYS}
        if cfg_over:
            cfg = cfg.scaled(**cfg_over)
    model = build_model(cfg)
    plan = {**train_plan(cfg), **plan_over}
    small_batch = wl.batch < mesh.shape.get("data", 1)
    # decode cells whose KV cache is sequence-sharded (KV heads don't divide
    # TP) also contraction-shard the attn projections — see ShardingPolicy
    seq_sharded_cache = (
        wl.kind == "decode"
        and cfg.n_kv_heads > 0
        and cfg.n_kv_heads % mesh.shape.get("model", 1) != 0
    )
    policy = make_policy(
        mesh, cfg, fsdp=True, shard_cache_seq=small_batch,
        qkv_contraction=seq_sharded_cache,
    )
    ns = lambda spec: NamedSharding(mesh, spec)
    param_sds = model.param_shapes()
    param_sh = jax.tree.map(ns, policy.param_specs(param_sds))

    hints = policy.hints()
    if wl.kind == "train" and plan["seq_shard_act"]:
        hints = dict(hints)
        hints["act_bsd"] = ns(P(policy.dp_axes, "model", None))

    if wl.kind == "train":
        opt_cfg = AdamWConfig(moment_dtype=plan["moment_dtype"])
        tc = TrainConfig(microbatches=plan["microbatches"])
        grad_sh = (
            jax.tree.map(ns, policy.param_specs(param_sds))
            if plan.get("shard_grad_accum", True) and tc.microbatches > 1
            else None
        )
        step = make_train_step(model, opt_cfg, tc, grad_shardings=grad_sh)
        from repro.optim import init_opt_state

        state_sds = {
            "params": param_sds,
            "opt": jax.eval_shape(lambda: init_opt_state(param_sds, opt_cfg)),
        }
        state_sh = jax.tree.map(ns, policy.state_specs(state_sds))
        batch_sds = model.input_specs("train", wl.batch, wl.seq)
        batch_sh = jax.tree.map(ns, policy.batch_specs(batch_sds))
        fn = jax.jit(
            step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
        return fn, (state_sds, batch_sds), hints

    if wl.kind == "prefill":
        batch_sds = model.input_specs("prefill", wl.batch, wl.seq)
        batch_sh = jax.tree.map(ns, policy.batch_specs(batch_sds))
        fn = jax.jit(
            lambda p, b: model.prefill(p, b),
            in_shardings=(param_sh, batch_sh),
        )
        return fn, (param_sds, batch_sds), hints

    if wl.kind == "decode":
        cache_sds = model.cache_shapes(wl.batch, wl.seq)
        cache_sh = jax.tree.map(ns, policy.cache_specs(cache_sds))
        tok_sds = SDS((wl.batch,), jnp.int32)
        tok_sh = ns(P(policy.dp_axes if not small_batch else None))
        fn = jax.jit(
            lambda p, c, t: model.decode_step(p, c, t),
            in_shardings=(param_sh, cache_sh, tok_sh),
            out_shardings=(None, cache_sh),
            donate_argnums=(1,),
        )
        return fn, (param_sds, cache_sds, tok_sds), hints

    raise ValueError(wl.kind)


# --------------------------------------------------------------------------
def run_cell(arch: str, shape: str, multi_pod: bool, coded: bool = False,
             overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    wl = SHAPES[shape]
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    try:
        fn, args_sds, hints = build_cell(cfg, wl, mesh, coded=coded,
                                         overrides=overrides)
        with mesh, sharding_hints(hints):
            lowered = fn.lower(*args_sds)
            compiled = lowered.compile()
        t_compile = time.time() - t0

        cost = compiled.cost_analysis() or {}
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        # cost_analysis counts while bodies ONCE; analyze_hlo expands trip
        # counts structurally (utils/hlo.py) — it is the roofline source.
        costs = analyze_hlo(hlo)
        mflops = model_flops(cfg, wl) / chips
        rl = roofline(costs.flops, costs.hbm_bytes, costs.wire_bytes,
                      model_flops=mflops)
        coll = costs.stats

        mem_d = {}
        if mem is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes"):
                v = getattr(mem, k, None)
                if v is not None:
                    mem_d[k] = int(v)
        result = {
            "arch": arch, "shape": shape, "multi_pod": multi_pod,
            "coded": coded, "status": "ok", "chips": chips,
            "compile_s": round(t_compile, 1),
            "memory": mem_d,
            "cost_xla_body_once": {
                k: cost[k] for k in ("flops", "bytes accessed") if k in cost
            },
            "collectives": {
                "bytes_by_op": coll.bytes_by_op,
                "count_by_op": coll.count_by_op,
                "wire_bytes": coll.wire_bytes,
            },
            "roofline": rl.as_dict(),
        }
        print(f"[dryrun] {arch} x {shape} x {'2pod' if multi_pod else '1pod'}"
              f"{' coded' if coded else ''}: OK "
              f"compile={t_compile:.0f}s dominant={rl.dominant} "
              f"bound={rl.bound_s*1e3:.2f}ms mfu_bound={rl.mfu_bound:.2%}")
        print(f"  memory_analysis: {mem_d}")
        print(f"  hlo_analysis: flops={costs.flops:.3e} bytes={costs.hbm_bytes:.3e} "
              f"wire={coll.wire_bytes:.3e}")
        return result
    except Exception as e:  # noqa: BLE001 — report failures as data
        print(f"[dryrun] {arch} x {shape} x {'2pod' if multi_pod else '1pod'}: "
              f"FAIL {type(e).__name__}: {e}")
        return {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                "coded": coded, "status": "error",
                "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2000:]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all)")
    ap.add_argument("--multi-pod", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--coded", action="store_true",
                    help="enable the BPCC coded serving head")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="write JSON report here")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already ok/skipped in --out")
    ap.add_argument("--set", action="append", default=[], dest="overrides",
                    help="perf knob: key=value (int/bool/str inferred); "
                         "repeatable — e.g. --set onehot_ce=1 --set microbatches=4")
    ap.add_argument("--dry-run", action="store_true",
                    help="list the resolved (arch x shape x mesh) cells with "
                         "applicability and the per-arch train plan, without "
                         "lowering or compiling anything")
    args = ap.parse_args()

    overrides = {}
    for kv in args.overrides:
        k, _, v = kv.partition("=")
        if v.lower() in ("true", "false"):
            overrides[k] = v.lower() == "true"
        else:
            try:
                overrides[k] = int(v)
            except ValueError:
                overrides[k] = v
    if overrides:
        print(f"[dryrun] overrides: {overrides}")

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.multi_pod]

    if args.dry_run:
        # resolved-plan listing, no device work: the config-validation idiom
        n_cells = 0
        for arch in archs:
            cfg = get_config(arch)
            plan = train_plan(cfg)
            n, _ = cfg.param_count()
            print(f"[dryrun] --dry-run {arch}: family={cfg.family} "
                  f"params~{n:,.0f} train_plan={plan}")
            for shape in shapes:
                ok, why = applicable(cfg, shape)
                for mp in pods:
                    tag = "2pod" if mp else "1pod"
                    status = "ok" if ok else f"skip ({why})"
                    print(f"    x {shape} x {tag}"
                          f"{' coded' if args.coded else ''}: {status}")
                    n_cells += ok
        print(f"[dryrun] --dry-run: {n_cells} compilable cells resolved; "
              f"nothing compiled")
        return

    done: set = set()
    if args.resume and args.out and os.path.exists(args.out):
        with open(args.out) as f:
            for r in json.load(f):
                if r["status"] in ("ok", "skipped"):
                    done.add((r["arch"], r["shape"], r["multi_pod"],
                              r.get("coded", False)))
        print(f"[dryrun] resume: {len(done)} cells already complete")

    key = lambda r: (r["arch"], r["shape"], r["multi_pod"], r.get("coded", False))

    def persist(results):
        if not args.out:
            return
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        existing = []
        if os.path.exists(args.out):
            with open(args.out) as f:
                existing = json.load(f)
        merged = {key(r): r for r in existing}
        for r in results:
            merged[key(r)] = r
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(list(merged.values()), f, indent=1)
        os.replace(tmp, args.out)

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                if (arch, shape, mp, args.coded) in done:
                    continue
                results.append(run_cell(arch, shape, mp, coded=args.coded,
                                        overrides=overrides or None))
                persist(results)  # incremental: survive kills/restarts
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} failed")
    if args.out:
        print(f"[dryrun] wrote {args.out}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
