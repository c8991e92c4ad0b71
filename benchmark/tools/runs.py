#!/usr/bin/env python3
"""Several runs of one cell in one call, each a new process (this parent
never touches JAX, so each child has the chip to itself), their result lines
kept in chiprun_out/runs/<tag>.jsonl and the spread of every metric printed:
the distance between the first and third quartile over the median.

    python3 benchmark/tools/runs.py --workload <cell> --seeds 1,2,3,4,5,6 \
        --trace 0 --tag set1 [--seconds <run_seconds>]
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values):
    if len(values) < 3 or statistics.median(values) == 0:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--seconds", default=None)
    ap.add_argument("--tag", default="runs")
    a = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = a.seconds or str(bench["run_seconds"])
    out_dir = os.path.join(ROOT, "chiprun_out", "runs")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{a.workload}.{a.tag}.jsonl")
    rows = []
    for seed in a.seeds.split(","):
        t0 = time.time()
        p = subprocess.run(
            bench["command"] + ["--workload", a.workload, "--seed", seed,
                                "--seconds", seconds, "--trace", a.trace],
            cwd=ROOT, capture_output=True, text=True)
        lines = [l for l in p.stdout.splitlines() if l.strip()]
        try:
            row = json.loads(lines[-1])
        except (IndexError, ValueError):
            row = {"error": p.stderr[-3000:], "stdout": p.stdout[-1000:]}
        row.update(seed=int(seed), rc=p.returncode, wall_s=time.time() - t0,
                   trace=int(a.trace))
        rows.append(row)
        with open(path, "a") as f:
            f.write(json.dumps(row) + "\n")
        brief = {k: row.get(k) for k in ("seed", "rc", "correct", "attempted",
                                        "failed", "wall_s")}
        brief["metrics"] = {k: v["value"] for k, v in
                            row.get("metrics", {}).items()}
        brief["compared"] = {k: v["value"] for k, v in
                             row.get("compared", {}).items()}
        if "device" in row:
            brief["device"] = row["device"]
        if "error" in row:
            brief["error"] = row["error"][-1500:]
        print(json.dumps(brief), flush=True)
    names = sorted({k for r in rows for k in r.get("metrics", {})})
    for n in names:
        vals = [r["metrics"][n]["value"] for r in rows if n in r.get("metrics", {})]
        first_out = vals[1:] if n == "setup_s" and len(vals) > 3 else vals
        sp = spread(first_out)
        print(f"SPREAD {a.workload} {a.tag} {n}: n={len(vals)} median="
              f"{statistics.median(vals):.6g} min={min(vals):.6g} "
              f"max={max(vals):.6g} iqr/median="
              f"{'n/a' if sp is None else f'{sp:.4%}'}", flush=True)
    return 0 if all(r.get("rc") == 0 and r.get("correct") for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
