"""The readings a limit is set from, in one process on the chip.

    python3 -m chipbench.tools.control --workload <cell> --seconds <s> \
        --seeds 1,2,...   --control-seeds 1,2,3

For each seed one run of the cell (a short window at the cell's own
load); for the control seeds also the control of "How correct is
decided": the plain reference with its matmul operands in float8-e4m3,
the precision below the configuration's bfloat16, put in the program's
place (8-bit integers with a scale per row do not separate from
bfloat16: PERF.md section 6).
Prints, per number compared, the largest the sound runs gave and the
smallest the control gave, and writes every run to
``chiprun_out/control_<cell>.jsonl``. Not a benchmark run: the benchmark
never runs the control.
"""
import argparse
import json
import os
import sys

from chipbench import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    os.makedirs(os.path.join(harness.ROOT, "chiprun_out"), exist_ok=True)
    out = os.path.join(harness.ROOT, "chiprun_out",
                       f"control_{args.workload.replace(':', '.')}.jsonl")
    sound, low = {}, {}
    with open(out, "a", encoding="utf-8") as fh:
        for seed in seeds:
            line, detail = harness.run(
                bench, args.workload, seed, args.seconds, False,
                control="fp8" if seed in control else None)
            fh.write(json.dumps({"seed": seed, "line": line, **detail}) + "\n")
            fh.flush()
            for c in detail["checks"]:
                sound.setdefault(c["name"], []).append(c["value"])
            for k, v in detail["notes"].items():
                if k.startswith("control."):
                    low.setdefault(k[len("control."):], []).append(v)
    for name, vals in sound.items():
        row = f"{name}: sound largest {max(vals):.6g} over {len(vals)} seeds"
        if name in low:
            row += (f"; control smallest {min(low[name]):.6g} over "
                    f"{len(low[name])} seeds (all: "
                    f"{[float(f'{v:.4g}') for v in low[name]]})")
        print(row, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
