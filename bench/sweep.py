"""The rate sweep that found an open-loop cell's knee (on the chip).

    python3 bench/sweep.py --workload phi3-chat --rates 4,6,8 --seconds 20 --seeds 1,2

One process; for each offered rate and each seed, one run of the cell's
traffic at that rate.  Prints per run: completed tokens/s, the cell's
end-to-end metrics (the tails), the mean queue wait in each third of the
window (a backlog that grows shows as rising thirds), and the requests
that never finished.  The knee is the highest rate whose queue does not
grow; several seeds at one rate show how far its tails repeat.
"""
import time

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1", help="e.g. 1 or 5,9,2000")
    args = ap.parse_args(argv)

    from harness import env

    env.init()
    from harness import cell, spec

    bench = spec.load(env.ROOT)
    err = env.chip_error(bench.cell(args.workload)["chips"])
    if err:
        print(f"sweep: {err}. Nothing was run.", file=sys.stderr)
        return 2
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in (int(s) for s in args.seeds.split(",")):
            out = cell.run(args.workload, seed, args.seconds, False,
                           t_process=time.monotonic(), bench=bench,
                           overrides={"traffic": {"rate_per_s": rate}})
            d = out["diag"]
            print(json.dumps({
                "rate_per_s": rate, "seed": seed, "attempted": out["attempted"],
                "failed": out["failed"], "correct": out["correct"],
                "tokens_per_s": d["tokens_in_window"] / args.seconds,
                "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                "queue_wait_ms_by_third": d["queue_wait_ms_by_third"],
                "compiles_in_window": out["compiles"]["window"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
