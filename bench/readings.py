"""The readings that the limits of ``correct`` are set from (on the chip).

    python3 bench/readings.py --workload phi3-chat --seeds 101-112 --seconds 10

One process, one short run per seed at the cell's own load.  Each run
checks its finished requests as the benchmark does and, on the same
sample, reads the control: the reference one precision step below the
configuration (the module it names, ``bench/references/<reference>.py``).
One JSON line per seed, then the lower reading (the largest gap of the
program's sound runs) and the upper reading (the smallest gap of the
control).  The benchmark's own runs never read the control.
"""
import time

import argparse
import json
import sys


def seeds(spec: str) -> list[int]:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi) + 1)) if hi else [int(lo)]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-112 or 5,9,2000")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from harness import env

    env.init()
    from harness import cell, spec

    bench = spec.load(env.ROOT)
    err = env.chip_error(bench.cell(args.workload)["chips"])
    if err:
        print(f"readings: {err}. Nothing was run.", file=sys.stderr)
        return 2
    prog, ctrl = [], []
    for s in seeds(args.seeds):
        out = cell.run(args.workload, s, args.seconds, False,
                       t_process=time.monotonic(), bench=bench, control=True)
        gap = out["checks"]["max_logit_gap"]["value"]
        prog.append(gap)
        ctrl.append(out["diag"]["control_checks"]["max_logit_gap"]["value"])
        print(json.dumps({"seed": s, "max_logit_gap": gap, "control": ctrl[-1],
                          "correct": out["correct"],
                          "control_correct": out["diag"]["control_correct"],
                          "diag": out["diag"],
                          "metrics": out["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(prog),
                      "lower": max(prog), "upper": min(ctrl),
                      "program": prog, "control": ctrl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
