"""Smoke run of the serving main path on a TPU chip.

    python chip_smoke.py              # one chip: phi3-mini through ServeEngine
    python chip_smoke.py --chips 4    # the four-chip paths beside one chip

One chip: ``phi3-mini-3.8b`` at its published widths (32 layers, d_model
3072, vocab 32064) with the block-coded LM head (14 data + 2 parity blocks)
and bfloat16 parameters made from ``--seed``, serving 8 requests of 128
prompt tokens and 32 greedy new tokens on 8 slots of a 512-token cache.
Three phases run on the same parameters:

  * ``healthy`` — the coded head with every block present;
  * ``erased``  — 2 of the 16 blocks erased at every step through
    ``mask_fn`` (a seeded pair per step);
  * ``kernel``  — the same erasures through the compiled fused Pallas
    matmul+decode kernel (``head_kernel_mode="compile"``).

The erased and kernel phases must emit exactly the healthy phase's tokens,
and the healthy coded head must agree with the plain (uncoded) head on one
prompt.  Where a token differs, the healthy run's top-2 logit gap at that
position is printed and the run fails.

Four chips (``--chips 4``): the paths that exist only across chips, each
beside its one-chip reference, at phi3-mini's widths with depth cut to 2
layers so the one-chip reference of training fits one chip:

  * ``ServeEngine(mesh=...)`` with the coded head split over a 4-device
    ``model`` axis (4 blocks per device), under the same per-step
    erasures — tokens identical to the one-chip engine;
  * the pjit train step on a 2x2 ``(data, model)`` mesh — parameters after
    one AdamW step within ``TRAIN_PARAM_TOL`` of the one-chip step.

Everything runs in this one process: a chip belongs to one process.  The
numbers printed are those of a smoke run, not benchmark measurements.  The
last line is one JSON object naming the device; with no TPU, or when any
phase fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import defaultdict
from pathlib import Path

# the package lives beside this script; without it the run fails here
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import repro  # noqa: E402,F401  (pins the PRNG and places the compile cache)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ARCH = "phi3-mini-3.8b"
N_SLOTS, S_MAX, PROMPT_LEN, NEW_TOKENS = 8, 512, 128, 32
N_ERASED = 2
# the coded head against the plain head, relative to the largest logit: both
# contract the same bfloat16 weights in float32, so only summation order and
# the decode's float32 recovery weights separate them
HEAD_REF_RTOL = 1e-4
# one AdamW step moves a parameter by at most lr * (1 + weight decay); a
# gradient within rounding of zero may change sign between the one-chip and
# the 2x2 reduction order, so two such steps bound the difference
TRAIN_LR = 1e-3
TRAIN_PARAM_TOL = 2.5 * TRAIN_LR
# the loss is reduced in a different order on the mesh, from bfloat16
# activations (8 significant bits)
TRAIN_LOSS_RTOL = 1e-2
# parameters whose 2x2 update differs from one chip's by more than a tenth
# of a step: only gradients within rounding of zero, a small share
TRAIN_FLIP_SHARE = 0.05
# the jitted functions whose compiles are reported by name: model init, the
# engine's prefill and decode steps, the head check, the train state init
# and the train step
ENTRY_POINTS = ("init", "_prefill_argmax", "_decode_argmax", "prefill",
                "init_state", "step")


class CompileLog:
    """XLA compile seconds per jitted function, from JAX's own events."""

    def __init__(self):
        self.seconds: dict[str, float] = defaultdict(float)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds[str(kw.get("fun_name", "?"))] += duration

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> dict[str, float]:
        """Seconds per entry point since the last call; the small programs
        of eager array operations are summed under "eager ops"."""
        out: dict[str, float] = defaultdict(float)
        for name, sec in self.seconds.items():
            fn = name.removeprefix("jit(").removesuffix(")")
            out[fn if fn in ENTRY_POINTS else "eager ops"] += sec
        self.seconds.clear()
        return {k: round(v, 3) for k, v in sorted(out.items())}


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def prompts(cfg, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, cfg.vocab, PROMPT_LEN).astype(np.int32)
        for _ in range(N_SLOTS)
    ]


def erasure_masks(n_blocks: int, n_steps: int, seed: int) -> list[np.ndarray]:
    """A seeded pair of erased blocks for each decode step."""
    rng = np.random.default_rng(seed + 1)
    masks = []
    for _ in range(n_steps):
        m = np.ones(n_blocks, np.float32)
        m[rng.choice(n_blocks, N_ERASED, replace=False)] = 0.0
        masks.append(m)
    return masks


def serve(model, params, reqs, *, masks=None, mesh=None,
          head_kernel_mode=None) -> tuple[dict[int, list[int]], int, float, dict]:
    """Serve ``reqs`` through ServeEngine; returns (tokens by uid, tokens
    emitted, wall seconds including compiles, param placement: leaf counts
    by the number of devices they span)."""
    from repro.serve import Request, ServeEngine

    mask_fn = None
    if masks is not None:
        it = iter(masks)
        mask_fn = lambda: next(it)  # noqa: E731  (one mask per decode step)
    eng = ServeEngine(
        model, params, n_slots=N_SLOTS, s_max=S_MAX, mask_fn=mask_fn,
        mesh=mesh, head_kernel_mode=head_kernel_mode,
    )
    for uid, p in enumerate(reqs):
        eng.submit(Request(uid=uid, prompt=p.copy(), max_new_tokens=NEW_TOKENS))
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    out = {r.uid: list(r.out_tokens) for r in done}
    if sorted(out) != list(range(len(reqs))) or any(
        len(t) != NEW_TOKENS for t in out.values()
    ):
        raise RuntimeError(f"engine returned {[len(t) for t in out.values()]} "
                           f"tokens for {len(out)} of {len(reqs)} requests")
    if any(not 0 <= t < model.cfg.vocab for ts in out.values() for t in ts):
        raise RuntimeError("engine emitted a token outside the vocabulary")
    spans = describe_placement(eng.params, "serve params") if mesh else {}
    return out, eng.tokens_emitted, wall, spans


def top2_gap(model, params, prompt: np.ndarray, prefix: list[int]) -> float:
    """Healthy top-1 minus top-2 logit after ``prompt + prefix``."""
    seq = np.concatenate([prompt, np.asarray(prefix, np.int32)])[None]
    logits, _ = jax.jit(model.prefill)(params, {"tokens": jnp.asarray(seq)})
    top = np.sort(np.asarray(logits[0], np.float64))[-2:]
    return float(top[1] - top[0])


def compare_tokens(name, got, ref, model, params, reqs) -> bool:
    ok = True
    for uid, want in ref.items():
        have = got[uid]
        if have == want:
            continue
        ok = False
        i = next(k for k, (a, b) in enumerate(zip(have, want)) if a != b)
        gap = top2_gap(model, params, reqs[uid], want[:i])
        print(f"FAIL {name}: request {uid} token {i} is {have[i]}, healthy "
              f"{want[i]}; healthy top-2 logit gap there {gap!r}")
    return ok


def report(phase, tokens, wall, compiles, device) -> None:
    print(f"[smoke] {phase}: tokens={tokens} wall_s={wall!r} "
          f"compile_s={json.dumps(compiles)} "
          f"peak_bytes_in_use={peak_bytes(device)}", flush=True)


def check_head_reference(model, cfg, params, prompt) -> None:
    """The coded head's logits against the plain head on one prompt."""
    from repro.models.registry import build_model

    plain = build_model(cfg.scaled(coded=False))
    batch = {"tokens": jnp.asarray(prompt[None])}
    coded, _ = jax.jit(model.prefill)(params, batch)
    ref, _ = jax.jit(plain.prefill)(params, batch)
    coded, ref = np.asarray(coded, np.float64), np.asarray(ref, np.float64)
    if coded.shape != (1, cfg.vocab) or not np.isfinite(coded).all():
        raise RuntimeError(f"coded head logits: shape {coded.shape}, "
                           f"finite {np.isfinite(coded).all()}")
    err = float(np.abs(coded - ref).max() / np.abs(ref).max())
    print(f"[smoke] head reference: max |coded - plain| / max |plain| = "
          f"{err!r} (limit {HEAD_REF_RTOL})", flush=True)
    if not err <= HEAD_REF_RTOL or coded.argmax() != ref.argmax():
        raise RuntimeError("coded head disagrees with the plain head")


def one_chip(seed: int, log: CompileLog) -> bool:
    from repro.configs import get_config
    from repro.models.config import coded_blocks
    from repro.models.registry import build_model

    dev = jax.devices()[0]
    cfg = get_config(ARCH).scaled(coded=True, coded_parity=2,
                                  param_dtype="bfloat16")
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.key(seed)))
    report("init", 0, time.perf_counter() - t0, log.take(), dev)
    reqs = prompts(cfg, seed)
    check_head_reference(model, cfg, params, reqs[0])
    log.take()
    masks = erasure_masks(coded_blocks(cfg), NEW_TOKENS, seed)

    healthy, n, wall, _ = serve(model, params, reqs)
    report("healthy", n, wall, log.take(), dev)
    erased, n, wall, _ = serve(model, params, reqs, masks=masks)
    report("erased", n, wall, log.take(), dev)
    kernel, n, wall, _ = serve(model, params, reqs, masks=masks,
                               head_kernel_mode="compile")
    report("kernel", n, wall, log.take(), dev)
    ok = compare_tokens("erased", erased, healthy, model, params, reqs)
    ok &= compare_tokens("kernel", kernel, healthy, model, params, reqs)
    if ok:
        print(f"[smoke] erased and kernel phases: {len(reqs) * NEW_TOKENS} "
              f"tokens identical to the healthy phase", flush=True)
    return ok


def describe_placement(tree, name: str) -> dict[str, int]:
    """Print where each leaf lands (spec, devices, rows of its largest
    shard); return leaf counts by the number of devices they span."""
    spans: dict[str, int] = defaultdict(int)
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        n_dev = len(leaf.sharding.device_set)
        spans[f"{n_dev} devices"] += 1
        spec = getattr(leaf.sharding, "spec", None)
        shard = max(s.data.shape for s in leaf.addressable_shards)
        print(f"[smoke] {name}{jax.tree_util.keystr(path)}: shape "
              f"{tuple(leaf.shape)} spec {spec} on {n_dev} devices, "
              f"shard {tuple(shard)}")
    return dict(spans)


def four_chip_serve(seed: int, log: CompileLog) -> bool:
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.models.config import coded_blocks
    from repro.models.registry import build_model

    devs = jax.devices()
    cfg = get_config(ARCH).scaled(n_layers=2, coded=True, coded_parity=2,
                                  param_dtype="bfloat16")
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(seed))
    reqs = prompts(cfg, seed)
    masks = erasure_masks(coded_blocks(cfg), NEW_TOKENS, seed)
    ref, n, wall, _ = serve(model, params, reqs, masks=masks)
    report("serve one chip", n, wall, log.take(), devs[0])
    mesh = Mesh(np.array(devs[:4]), ("model",))
    got, n, wall, spans = serve(model, params, reqs, masks=masks, mesh=mesh)
    report("serve 4-chip head", n, wall, log.take(), devs[0])
    print(f"[smoke] serve param leaves by span {spans}", flush=True)
    ok = compare_tokens("4-chip head", got, ref, model, params, reqs)
    if set(spans) != {"4 devices"}:
        print("FAIL serve placement: a parameter leaf does not span the mesh")
        ok = False
    if ok:
        print(f"[smoke] 4-chip coded head: {len(reqs) * NEW_TOKENS} tokens "
              f"identical to one chip", flush=True)
    return ok


def four_chip_train(seed: int, log: CompileLog) -> bool:
    from jax.sharding import AxisType, NamedSharding, SingleDeviceSharding

    from repro.configs import get_config
    from repro.data import make_pipeline
    from repro.models.registry import build_model
    from repro.optim import AdamWConfig
    from repro.sharding.ctx import sharding_hints
    from repro.sharding.policy import make_policy
    from repro.train.loop import TrainConfig, init_train_state, make_train_step

    devs = jax.devices()
    cfg = get_config(ARCH).scaled(n_layers=2)
    model = build_model(cfg)
    opt = AdamWConfig(lr=TRAIN_LR)
    pipe = make_pipeline(cfg, seq=PROMPT_LEN, global_batch=N_SLOTS, seed=seed)
    batch = jax.tree.map(jnp.asarray, pipe.batch(0))
    step_fn = make_train_step(model, opt, TrainConfig())

    def init_state(key):
        return init_train_state(model, key, opt)

    one = SingleDeviceSharding(devs[0])
    t0 = time.perf_counter()
    s0 = jax.jit(init_state, out_shardings=one)(jax.random.key(seed))
    s1, m1 = jax.jit(step_fn)(s0, batch)
    p1 = jax.device_get(s1["params"])
    loss1 = float(m1["loss"])
    del s0, s1
    report("train one chip", 0, time.perf_counter() - t0, log.take(), devs[0])

    mesh = jax.make_mesh((2, 2), ("data", "model"), devices=devs[:4],
                         axis_types=(AxisType.Auto,) * 2)
    policy = make_policy(mesh, cfg)
    sds = jax.eval_shape(init_state, jax.random.key(seed))
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), policy.state_specs(sds))
    t0 = time.perf_counter()
    with mesh, sharding_hints(policy.hints()):
        sm = jax.jit(init_state, out_shardings=sh)(jax.random.key(seed))
        sm1, mm = jax.jit(step_fn, in_shardings=(sh, None),
                          out_shardings=(sh, None))(sm, batch)
        lossm = float(mm["loss"])
    report("train 2x2 mesh", 0, time.perf_counter() - t0, log.take(), devs[0])
    spans = describe_placement(sm1["params"], "train params")
    worst, flipped, total = 0.0, 0, 0
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(sm1["params"])):
        diff = np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
        worst = max(worst, float(diff.max()))
        flipped += int((diff > TRAIN_LR / 10).sum())
        total += diff.size
    loss_rel = abs(lossm - loss1) / abs(loss1)
    share = flipped / total
    print(f"[smoke] train 2x2 vs one chip: loss {lossm!r} vs {loss1!r} "
          f"(rel {loss_rel!r}, limit {TRAIN_LOSS_RTOL}); max |param diff| "
          f"{worst!r} (limit {TRAIN_PARAM_TOL}); share of params off by more "
          f"than lr/10 {share!r} (limit {TRAIN_FLIP_SHARE}); param leaves by "
          f"span {spans}", flush=True)
    ok = (worst <= TRAIN_PARAM_TOL and loss_rel <= TRAIN_LOSS_RTOL
          and share <= TRAIN_FLIP_SHARE)
    if set(spans) != {"4 devices"}:
        print("FAIL train placement: a parameter leaf does not span the mesh")
        ok = False
    return ok


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving phases; 4: the four-chip paths "
                    "beside their one-chip references")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    print(f"[smoke] device platform={platform} kind={kind!r} count={len(devs)}",
          flush=True)
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {platform}); nothing was run",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devs)}", file=sys.stderr)
        return 2
    log = CompileLog()
    if args.chips == 1:
        ok = one_chip(args.seed, log)
    else:
        ok = four_chip_serve(args.seed, log)
        ok &= four_chip_train(args.seed, log)
    print(f"[smoke] persistent compile cache hits: {log.cache_hits}")
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
