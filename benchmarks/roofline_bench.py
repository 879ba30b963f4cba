"""Roofline table from the dry-run report -> roofline.json.

Derives the three terms per (arch x shape x mesh) cell and the dominant
bottleneck — this is the §Roofline source of EXPERIMENTS.md.

The dry-run report is self-generating: when neither the committed
``reports/dryrun.json`` (the full ``--all`` sweep, refreshed manually) nor
a previously generated ``$BENCH_REPORT_DIR/dryrun.json`` exists, this
bench INVOKES ``repro.launch.dryrun`` itself on the smallest arch
(mamba2-130m; one shape in quick mode, the three short shapes otherwise)
and proceeds from that — the bench can no longer "pass" by silently
skipping (the green-wash this file used to print).  Each cell is a
subprocess: the dryrun launcher must install its 512-device XLA flag
before the first jax import, which cannot happen in-process here.

``--strict`` (or ``ROOFLINE_STRICT=1``, set by CI) turns any
missing-report / failed-generation condition into a nonzero exit.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import REPORT_DIR, emit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMMITTED_REPORT = os.path.join(REPO, "reports", "dryrun.json")

GEN_ARCH = "mamba2-130m"  # smallest registry arch: ~4 s/cell on this host
GEN_SHAPES_QUICK = ["decode_32k"]
GEN_SHAPES_FULL = ["train_4k", "prefill_32k", "decode_32k"]


def _generate(out_path: str, quick: bool) -> bool:
    """Run the dryrun launcher per cell (subprocess — it must set its XLA
    device-count flag pre-import) and merge the cell reports."""
    shapes = GEN_SHAPES_QUICK if quick else GEN_SHAPES_FULL
    cells: list[dict] = []
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.join(REPO, "src"), env.get("PYTHONPATH")] if p
    )
    # the parent (benchmarks.run) already holds the accelerator; the dry-run
    # compiles for placeholder CPU devices and must never reach for it
    env["JAX_PLATFORMS"] = "cpu"
    for shape in shapes:
        tmp = f"{out_path}.{shape}.part"
        cmd = [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", GEN_ARCH, "--shape", shape,
            "--multi-pod", "single", "--out", tmp,
        ]
        print(f"# roofline: generating dry-run cell {GEN_ARCH} x {shape}")
        proc = subprocess.run(cmd, cwd=REPO, env=env,
                              capture_output=True, text=True)
        if proc.returncode != 0:
            print(f"# roofline: dryrun failed for {shape}:\n{proc.stderr[-2000:]}")
            return False
        with open(tmp) as f:
            cells.extend(json.load(f))
        os.remove(tmp)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(cells, f, indent=1)
    return True


def run(quick: bool = False, strict: bool | None = None) -> None:
    if strict is None:
        strict = os.environ.get("ROOFLINE_STRICT", "") not in ("", "0")
    report = COMMITTED_REPORT
    if not os.path.exists(report):
        report = os.path.join(REPORT_DIR, "dryrun.json")
        if not os.path.exists(report):
            if not _generate(report, quick):
                msg = ("# roofline: no dry-run report and self-generation "
                       "failed")
                if strict:
                    raise SystemExit(msg)
                print(msg + " — skipping (set --strict to fail)")
                return
    with open(report) as f:
        cells = json.load(f)
    rows = []
    for c in cells:
        if c.get("status") != "ok":
            rows.append({"arch": c["arch"], "shape": c["shape"],
                         "mesh": "2pod" if c["multi_pod"] else "1pod",
                         "status": c["status"],
                         "compute_ms": "", "memory_ms": "", "collective_ms": "",
                         "dominant": c.get("reason", c.get("error", ""))[:40],
                         "useful_frac": "", "mfu_bound": ""})
            continue
        rl = c["roofline"]
        rows.append({
            "arch": c["arch"], "shape": c["shape"],
            "mesh": "2pod" if c["multi_pod"] else "1pod",
            "status": "ok",
            "compute_ms": rl["compute_s"] * 1e3,
            "memory_ms": rl["memory_s"] * 1e3,
            "collective_ms": rl["collective_s"] * 1e3,
            "dominant": rl["dominant"],
            "useful_frac": rl["useful_fraction"],
            "mfu_bound": rl["mfu_bound"],
        })
    rows.sort(key=lambda r: (r["arch"], r["shape"], r["mesh"]))
    if strict and not rows:
        raise SystemExit("# roofline: dry-run report produced zero cells")
    emit("roofline", rows)


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero instead of skipping when the "
                         "dry-run report is missing and ungenerable")
    args = ap.parse_args()
    run(quick=args.quick, strict=args.strict or None)


if __name__ == "__main__":
    main()
