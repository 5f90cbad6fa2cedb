"""Find the knee of an open-loop cell once, by a sweep on the chip.

    python3 -m chipbench.tools.sweep_rate \
        --workload cerebras-gpt-1.3b:chat-poisson \
        --rates 0.5,1,1.5,2,3 --seconds 30

Runs the cell's own job at each offered rate (a changed copy of its
traffic file; nothing else differs) and prints, per rate: offered and
completed tokens/s, requests finished / submitted, TTFT p50/p95, ITL p95.
The knee is the highest rate the system sustains: completed tokens/s
still follows the offered load and the TTFT median has not begun to grow
with the length of the run. The cell's fixed rate is four fifths of it,
written into the traffic file as a number. Not a benchmark run.
"""
import argparse
import copy
import json
import os
import sys

from chipbench import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=20230923)
    ap.add_argument("--engine", default="",
                    help="engine sizing to try, e.g. max_batch=8,num_blocks=256")
    args = ap.parse_args(argv)
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.Cell(bench, args.workload)
    base, config = cell.traffic, copy.deepcopy(cell.config)
    for pair in filter(None, args.engine.split(",")):
        key, value = pair.split("=")
        config["engine"][key] = int(value)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = copy.deepcopy(base)
        traffic["arrivals"]["rate"] = rate
        traffic["check_requests"] = 1
        line, detail = harness.run(bench, args.workload, args.seed,
                                   args.seconds, False, traffic=traffic,
                                   config=config)
        row = {"engine": config["engine"], "rate": rate, "attempted": line["attempted"],
               "failed": line["failed"], "correct": line["correct"],
               **detail["end_to_end"]}
        rows.append(row)
        print("sweep " + json.dumps(row), flush=True)
    os.makedirs(os.path.join(harness.ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(harness.ROOT, "chiprun_out",
                           f"sweep_{args.workload.replace(':', '.')}.jsonl"), "a") as fh:
        for row in rows:
            fh.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
