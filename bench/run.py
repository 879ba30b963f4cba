"""Run one cell of the benchmark on the chip this process holds.

    python3 bench/run.py --workload phi3-chat --seed 7 --seconds 45 --trace 0

The cell (``BENCHMARK.json``) names a configuration and a traffic mix;
``harness/cell.py`` does the run.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones) and
``device``; the numbers that decided ``correct`` come last, under
``checks``, and again as the last lines of standard error.

No TPU, fewer chips than the cell asks for, or a device missing from the
peaks table: exit 2 and no result.  The run never falls back to the CPU.
JAX's persistent compilation cache lives at ``.jax_cache/`` in the
checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), and every program
is written to it, however fast it compiled.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402


def _finite(x):
    """JSON has no infinity or NaN: such a number is written as null."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import env

    env.init()
    from harness import cell, spec

    bench = spec.load(env.ROOT)
    err = env.chip_error(bench.cell(args.workload)["chips"])
    if err:
        print(f"bench: {err}. Nothing was run.", file=sys.stderr)
        return 2
    out = cell.run(args.workload, args.seed, args.seconds, bool(args.trace),
                   t_process=T_PROCESS, bench=bench)
    print(json.dumps(_finite(out)), flush=True)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
