"""Measure a cell the way its bounds are set: two sets of runs with the
same seeds, every run a process of its own through the real command.

    python3 -m chipbench.tools.run_sets --workload <cell> --seeds 1,2,3,4,5,6 \
        [--sets 2] [--seconds <run_seconds>] [--traced-seed 7]

Prints every result line, then per end-to-end metric each set's median and
spread (distance between the quartiles of ``statistics.quantiles(n=4)`` as
a share of the median) and the bound five times the wider spread would
give. Appends the lines to ``chiprun_out/sets_<cell>.jsonl``. This parent
process never touches jax, so each child has the chip to itself.
"""
import argparse
import json
import os
import subprocess
import sys
import time

from chipbench import stats

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def one(workload, seed, seconds, trace):
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], cwd=ROOT, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    for text in lines:
        if text.startswith("[chipbench]") and (
                "check " in text or "p50" in text or "window:" in text
                or "service time" in text):
            print("   " + text[:300], flush=True)
    if p.returncode != 0 or not lines:
        print(f"run seed={seed} FAILED rc={p.returncode}\n{p.stderr[-2000:]}",
              flush=True)
        return None
    line = json.loads(lines[-1])
    print(f"run seed={seed} trace={trace} wall={time.time() - t0:.1f}s "
          + lines[-1][:3000], flush=True)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--traced-seed", type=int, default=None)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bench = json.load(fh)
    seconds = args.seconds or bench["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = os.path.join(ROOT, "chiprun_out", f"sets_{args.workload}.jsonl")
    sets = []
    with open(out, "a", encoding="utf-8") as fh:
        for n in range(args.sets):
            rows = []
            for seed in seeds:
                line = one(args.workload, seed, seconds, 0)
                if line is not None:
                    rows.append(line)
                    fh.write(json.dumps({"set": n, "seed": seed, **line})
                             + "\n")
                    fh.flush()
            sets.append(rows)
        if args.traced_seed is not None:
            line = one(args.workload, args.traced_seed, seconds, 1)
            if line is not None:
                fh.write(json.dumps({"set": "traced",
                                     "seed": args.traced_seed, **line}) + "\n")
    names = sorted({k for rows in sets for r in rows for k in r["metrics"]})
    for name in names:
        spreads = []
        for n, rows in enumerate(sets):
            vals = [r["metrics"][name]["value"] for r in rows
                    if name in r["metrics"]]
            if len(vals) < 3:
                continue
            spreads.append(stats.iqr_share(vals))
            print(f"{name} set {n}: median {stats.median(vals):.6g} spread "
                  f"{100 * spreads[-1]:.3f}% n={len(vals)} "
                  f"values {[float(f'{v:.6g}') for v in vals]}", flush=True)
        if spreads:
            print(f"{name}: wider spread {100 * max(spreads):.3f}% -> bound "
                  f"~{100 * max(0.01, 5 * max(spreads)):.2f}%", flush=True)
    bad = [r for rows in sets for r in rows if not r["correct"] or r["failed"]]
    print(f"runs {sum(map(len, sets))}, not correct or with failures: "
          f"{len(bad)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
