"""Run one cell several times, one process per run, and summarise.

    python3 benchmarks/chip/sets.py --workload <name> --seeds 11,12,13 \
        --seconds 10 [--trace 0] [--out runs.jsonl]

Each run is ``run.py`` in a fresh process, as the check runs it; this
parent never imports JAX, so each child holds the chip alone. Every
result line (with the run's seed, exit code, wall time and the tail of its
standard error) is appended to ``--out``; the summary gives, per metric,
the median and the spread: the distance between the first and third
quartiles (``statistics.quantiles(values, n=4)``) over the median.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--timeout", type=float, default=1200)
    args = ap.parse_args(argv)
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        cmd = [sys.executable, str(RUN), "--workload", args.workload,
               "--seed", str(seed), "--seconds", str(args.seconds),
               "--trace", str(args.trace)]
        t0 = time.perf_counter()
        try:
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.timeout)
            rc, out, err = p.returncode, p.stdout, p.stderr
        except subprocess.TimeoutExpired as e:
            rc, out, err = 124, e.stdout or "", e.stderr or ""
            out = out.decode() if isinstance(out, bytes) else out
            err = err.decode() if isinstance(err, bytes) else err
        wall = time.perf_counter() - t0
        last = out.strip().splitlines()[-1] if out.strip() else ""
        try:
            res = json.loads(last)
        except json.JSONDecodeError:
            res = None
        row = {"workload": args.workload, "seed": seed, "trace": args.trace,
               "rc": rc, "wall_s": wall, "result": res,
               "stderr_tail": err[-6000:]}
        rows.append(row)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
        info = [l for l in err.splitlines() if l.startswith(("[info]", "[check]", "[data]"))]
        print(f"--- seed {seed} rc {rc} wall {wall:.1f}s", flush=True)
        print("\n".join(info[-12:]), flush=True)
        if res is None:
            print(err[-3000:], flush=True)
        else:
            print(json.dumps({k: res[k] for k in ("correct", "metrics")}),
                  flush=True)
    ok = [r["result"] for r in rows if r["result"]]
    names = sorted({m for r in ok for m in r["metrics"]})
    for m in names:
        vals = [r["metrics"][m]["value"] for r in ok if m in r["metrics"]]
        print(f"[summary] {args.workload} {m}: n={len(vals)} median="
              f"{statistics.median(vals)!r} spread={spread(vals)!r} "
              f"values={vals!r}", flush=True)
    print(f"[summary] {args.workload} correct "
          f"{sum(bool(r['correct']) for r in ok)}/{len(rows)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
