"""Serving launcher: batched decode with the BPCC coded head.

    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --smoke \\
        --requests 16 --coded --straggler-prob 0.2

Continuous batching (``serve.engine.ServeEngine``): a fixed decode batch of
``--slots`` sequences, finished slots immediately refilled from the queue.
With ``--coded`` the LM-head matvec runs through the block-coded path — up
to ``--parity`` tensor-parallel shards may straggle or die per step and the
logits stay exact (DESIGN.md §2/§5).  With ``--adaptive-parity`` the number
of shards dropped per step is chosen from the recent straggler posterior
(``core.adaptive.ParityController``, DESIGN.md §8) instead of always
dropping the ``--parity`` slowest.

``--dry-run`` prints the fully-resolved serving configuration (model
config, coded-head geometry, engine settings) and exits without building
the model or executing a single step — the config-validation idiom.

``--profile DIR`` records a ``jax.profiler`` trace of the serve loop in
DIR: the engine's host spans (``engine.step``, ``engine.admit``,
``engine.prefill``, ``engine.splice``, ``engine.control``,
``engine.launch``, ``engine.sync``, ``engine.apply``) beside the device's
operations.  At the end it prints, for each compiled step, how many of
its operations fall under the model's named scopes
(``ServeEngine.op_scopes()``), which is how a device operation in the
trace is traced back to the coded head or the KV-cache write.
"""
from __future__ import annotations

import argparse
import contextlib
import time
from collections import Counter

import numpy as np

SCOPES = ("coded_head", "kv_write")  # the model's jax.named_scopes


def _profiled(log_dir: str | None):
    """A profiler trace into ``log_dir`` around the serve loop, or nothing."""
    if log_dir is None:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.trace(log_dir)


def _print_scopes(eng) -> None:
    """Operations per named scope of each compiled step."""
    for prog, ops in eng.op_scopes().items():
        n = Counter(next((s for s in SCOPES if f"/{s}/" in op), "unscoped")
                    for op in ops.values())
        print(f"  {prog} operations: "
              + "  ".join(f"{k} {v}" for k, v in sorted(n.items())))


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Batched LM serving with the BPCC coded head",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    ap.add_argument("--arch", default="glm4-9b",
                    help="model architecture id (see repro.configs.ARCHS)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced model config sized for the CPU container")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of synthetic requests to serve")
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous-batching decode slots (batch size)")
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="tokens per synthetic prompt")
    ap.add_argument("--max-new", type=int, default=32,
                    help="max new tokens generated per request")
    ap.add_argument("--s-max", type=int, default=128,
                    help="KV-cache capacity (max sequence length) per slot")
    ap.add_argument("--coded", action="store_true",
                    help="BPCC coded LM head (straggler-tolerant logits)")
    ap.add_argument("--parity", type=int, default=2,
                    help="parity shards of the coded head (erasure budget)")
    ap.add_argument("--straggler-prob", type=float, default=0.0,
                    help="per-step probability each TP shard's result is lost")
    ap.add_argument("--adaptive-parity", action="store_true",
                    help="pick the per-step parity level from the online "
                         "straggler posterior (DESIGN.md §8) instead of "
                         "always dropping the full parity budget")
    ap.add_argument("--trace", choices=["none", "poisson", "bursty"],
                    default="none",
                    help="open-loop arrival trace (DESIGN.md §10): requests "
                         "arrive over wall-clock time with per-request "
                         "deadlines and admission control, instead of a "
                         "pre-loaded queue")
    ap.add_argument("--rate", type=float, default=2.0,
                    help="trace mode: mean arrival rate, requests/second")
    ap.add_argument("--slo-factor", type=float, default=4.0,
                    help="trace mode: per-token deadline budget as a "
                         "multiple of the nominal step time")
    ap.add_argument("--t-token-est", type=float, default=0.05,
                    help="trace mode: nominal per-token wall-clock seconds "
                         "used to size deadlines (EW-corrected online)")
    ap.add_argument("--deadline-parity", action="store_true",
                    help="trace mode + --adaptive-parity: escalate the "
                         "parity level from SLO slack (DESIGN.md §10's "
                         "DeadlineAwareParity) rather than straggler "
                         "history alone")
    ap.add_argument("--tenants", type=int, default=1,
                    help="trace mode: SLO classes (DESIGN.md §13) — 1 is "
                         "the single default class; N>1 splits traffic "
                         "into N weighted-fair-queued tenants with "
                         "geometrically decaying weights and tightening "
                         "deadline factors")
    ap.add_argument("--tenant-parity", action="store_true",
                    help="with --deadline-parity and --tenants > 1: "
                         "per-class slack -> parity escalation "
                         "(TenantDeadlineParity) instead of the global "
                         "min-slack rule")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="trace mode: prompt tokens the engine may prefill "
                         "per step (prefill/decode disaggregation); "
                         "default refills every free slot")
    ap.add_argument("--macro-steps", type=int, default=1,
                    help="fused macro-step decode K_max (DESIGN.md §14): "
                         "decode up to K steps per jitted launch with one "
                         "host sync per block at batch-full steady state; "
                         "1 keeps the scalar per-token loop")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed (params, prompts, straggler draws)")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the resolved config and exit without executing")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="record a jax.profiler trace of the serve loop in DIR "
                         "(the engine's engine.* spans and the device's "
                         "operations) and print each compiled step's "
                         "operations per named scope")
    args = ap.parse_args()
    if args.adaptive_parity and not (args.coded and args.straggler_prob > 0):
        ap.error("--adaptive-parity requires --coded and --straggler-prob > 0 "
                 "(there is no straggler posterior to adapt to otherwise)")
    if args.deadline_parity and not (args.adaptive_parity and args.trace != "none"):
        ap.error("--deadline-parity requires --adaptive-parity and --trace "
                 "(SLO slack only exists under a deadline-bearing trace)")
    if args.tenants < 1:
        ap.error("--tenants must be >= 1")
    if args.tenant_parity and not (args.deadline_parity and args.tenants > 1):
        ap.error("--tenant-parity requires --deadline-parity and --tenants > 1")
    if args.macro_steps < 1:
        ap.error("--macro-steps must be >= 1")

    from repro.configs import get_config
    from repro.models.config import coded_blocks

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.coded:
        cfg = cfg.scaled(coded=True, coded_parity=args.parity)
    n_shards = coded_blocks(cfg)  # TP width of the coded LM head (jax-free)

    if args.dry_run:
        n_params, _ = cfg.param_count()
        print("[serve] --dry-run resolved config:")
        print(f"  arch={cfg.name} family={cfg.family} smoke={args.smoke} "
              f"params~{n_params:,.0f}")
        print(f"  d_model={cfg.d_model} n_layers={cfg.n_layers} "
              f"vocab={cfg.vocab}")
        print(f"  engine: slots={args.slots} s_max={args.s_max} "
              f"requests={args.requests} prompt_len={args.prompt_len} "
              f"max_new={args.max_new} macro_steps={args.macro_steps}")
        print(f"  coded={cfg.coded} parity={cfg.coded_parity if cfg.coded else 0} "
              f"shards={n_shards} straggler_prob={args.straggler_prob} "
              f"adaptive_parity={args.adaptive_parity}")
        if args.trace != "none":
            print(f"  traffic: trace={args.trace} rate={args.rate}/s "
                  f"slo_factor={args.slo_factor} t_token_est={args.t_token_est}s "
                  f"deadline_parity={args.deadline_parity} "
                  f"tenants={args.tenants} tenant_parity={args.tenant_parity} "
                  f"prefill_budget={args.prefill_budget}")
        return

    import jax

    from repro.core.adaptive import ParityController
    from repro.models.registry import build_model
    from repro.serve import Request, ServeEngine

    model = build_model(cfg)
    params = model.init(jax.random.key(args.seed))

    rng = np.random.default_rng(args.seed)
    mask_fn = None
    latency_fn = None
    controller = None
    if args.coded and args.straggler_prob > 0:
        if args.adaptive_parity:
            # synthetic per-shard latencies with randomly-straggling shards,
            # observed through the HealthMonitor's EW estimator: the mask is
            # committed from backward-looking ESTIMATES (what a real
            # deployment knows pre-step, DESIGN.md §10), while the posterior
            # decides how many laggards to drop each step
            from repro.runtime.health import HealthMonitor

            monitor = HealthMonitor(n_workers=n_shards)

            def latency_fn():
                lat = 1e-3 * (1.0 + 0.1 * rng.random(n_shards))
                slow = rng.random(n_shards) < args.straggler_prob
                lat[slow] *= 50.0
                monitor.observe_step_latencies(lat)
                return monitor.shard_latencies()

            controller = ParityController(n_shards)
        else:
            def mask_fn():
                m = np.ones(n_shards)
                drop = rng.random(n_shards) < args.straggler_prob
                # never drop more than the parity budget (a real deployment
                # would fall back to waiting for the slowest shard)
                idx = np.flatnonzero(drop)[: args.parity]
                m[idx] = 0.0
                return m

    if args.trace != "none":
        # ---- trace-driven mode: open-loop arrivals + deadlines ----------
        from repro.core.adaptive import DeadlineAwareParity, TenantDeadlineParity
        from repro.serve import (
            SLOClass,
            TraceScheduler,
            bursty_trace,
            poisson_trace,
        )

        classes = None
        if args.tenants > 1:
            # premium tenants: higher WFQ weight, tighter per-token SLO,
            # slacker escalation (they start hedging earlier)
            classes = tuple(
                SLOClass(name=f"t{c}", weight=2.0 ** (args.tenants - 1 - c),
                         slo_factor=args.slo_factor * (1.0 + 0.5 * c),
                         share=1.0, escalate_steps=8.0 * (1.0 + c))
                for c in range(args.tenants)
            )
        mk = poisson_trace if args.trace == "poisson" else bursty_trace
        trace = mk(args.rate, args.requests, seed=args.seed,
                   mean_tokens=args.max_new, max_tokens=args.max_new,
                   t_token=args.t_token_est, slo_factor=args.slo_factor,
                   classes=classes)
        payloads = [
            Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, args.prompt_len).astype(np.int32),
                    max_new_tokens=int(trace.n_tokens[i]))
            for i in range(trace.n_requests)
        ]
        sched = TraceScheduler(trace, args.slots, t_step_init=args.t_token_est,
                               payloads=payloads)
        policy = None
        if args.deadline_parity and controller is not None:
            policy = (TenantDeadlineParity(controller, classes=trace.classes)
                      if args.tenant_parity
                      else DeadlineAwareParity(controller))
        t0 = time.monotonic()
        clock = lambda: time.monotonic() - t0  # noqa: E731
        eng = ServeEngine(model, params, n_slots=args.slots, s_max=args.s_max,
                          mask_fn=mask_fn, latency_fn=latency_fn,
                          parity_controller=controller, parity_policy=policy,
                          scheduler=sched, clock=clock,
                          prefill_budget=args.prefill_budget,
                          macro_steps=args.macro_steps)
        with _profiled(args.profile):
            while not sched.finished:
                if eng.macro_step() == 0:
                    nxt = sched.next_arrival()
                    if nxt is None:
                        break
                    time.sleep(max(0.0, nxt - clock()))
        res = sched.results()
        dt = clock()
        n_tok = int(res["n_tokens"][np.isfinite(res["t_complete"])].sum())
        syncs_per_tok = eng.sync_count / max(eng.tokens_emitted, 1)
        print(f"[serve] trace={args.trace} {trace.n_requests} requests, "
              f"{n_tok} tokens in {dt:.2f}s ({n_tok / max(dt, 1e-9):,.1f} tok/s)")
        print(f"  SLO attainment {res['slo_met'].mean():.1%}  "
              f"rejected {int(res['rejected'].sum())}  "
              f"est_step {sched.est_step_time * 1e3:.1f} ms  "
              f"deadline_parity={policy is not None}")
        print(f"  macro_steps={args.macro_steps}  fused_blocks={eng.macro_blocks}  "
              f"host_syncs/token={syncs_per_tok:.3f}")
        if args.tenants > 1:
            for c, cls in enumerate(trace.classes):
                sel = res["tenant"] == c
                att = res["slo_met"][sel].mean() if sel.any() else 1.0
                print(f"  class {cls.name}: weight={cls.weight:g} "
                      f"n={int(sel.sum())} attainment {att:.1%}")
        if args.profile:
            _print_scopes(eng)
        return

    eng = ServeEngine(model, params, n_slots=args.slots, s_max=args.s_max,
                      mask_fn=mask_fn, latency_fn=latency_fn,
                      parity_controller=controller,
                      macro_steps=args.macro_steps)
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, size=args.prompt_len).astype(np.int32)
        eng.submit(Request(uid=i, prompt=prompt, max_new_tokens=args.max_new))
    t0 = time.time()
    with _profiled(args.profile):
        done = eng.run()
    dt = time.time() - t0
    n_tok = sum(len(r.out_tokens) for r in done)
    syncs_per_tok = eng.sync_count / max(eng.tokens_emitted, 1)
    print(f"[serve] {len(done)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:,.1f} tok/s) coded={args.coded} "
          f"straggler_prob={args.straggler_prob} "
          f"adaptive_parity={controller is not None} "
          f"macro_steps={args.macro_steps} "
          f"host_syncs/token={syncs_per_tok:.3f}")
    for r in done[:3]:
        print(f"  req {r.uid}: {r.out_tokens[:10]}...")
    if args.profile:
        _print_scopes(eng)


if __name__ == "__main__":
    main()
