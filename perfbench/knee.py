#!/usr/bin/env python3
"""Find a take cell's knee and fix its offered rate at four fifths of it.

    python3 perfbench/knee.py run --workload take-zipf --seed 11 \\
        --rates 2000,4000,8000,16000 --seconds 5         # on the chip
    python3 perfbench/knee.py apply --workload take-zipf  # here, after

``run`` boots the node once and offers each rate in turn
(``run.py --sweep``), writing every step to
``chiprun_out/perfbench/knee-<workload>.json``. ``apply`` reads that
file, takes as the knee the highest rate whose takes were all answered,
whose latency did not climb from the step's first quarter to its last
(no growing backlog: the last quarter's p99 within 1.25 x the first's
plus 50 ms), and whose p99 stayed within 20 ms (the default timeout of
Envoy's HTTP rate-limit filter); where no rate meets 20 ms, the knee is
the backlog limit alone. It writes 0.8 x knee into the cell's traffic
file and prints the sweep as a table for PERF.md.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
P99_LIMIT_MS = 20.0
SHARE = 0.8


def sweep_file(workload: str) -> str:
    return os.path.join(CHECKOUT, "chiprun_out", "perfbench", f"knee-{workload}.json")


def keeps_up(step: dict, p99_limit_ms: float = float("inf")) -> bool:
    return (
        step.get("drained", True)
        and step["answered"] == step["offered"]
        and step["p99_last_quarter_ms"] <= 1.25 * step["p99_first_quarter_ms"] + 50.0
        and step["p99_ms"] <= p99_limit_ms
    )


def knee(steps: list, p99_limit_ms: float = float("inf")):
    ok = [s["rate_per_s"] for s in steps if keeps_up(s, p99_limit_ms)]
    return max(ok) if ok else None


def table(steps: list) -> str:
    rows = ["| offered/s | answered in step | p50 ms | p99 ms | p99 first/last quarter ms | generator late p99 ms | keeps up |",
            "| --- | --- | --- | --- | --- | --- | --- |"]
    for s in steps:
        rows.append(
            f"| {s['rate_per_s']:g} | {s['answered_in_window']}/{s['offered']} | {s['p50_ms']:.3f} | "
            f"{s['p99_ms']:.3f} | {s['p99_first_quarter_ms']:.3f}/{s['p99_last_quarter_ms']:.3f} | "
            f"{s['generator_late_p99_ms']:.3f} | {'yes' if keeps_up(s) else 'no'} |"
        )
    return "\n".join(rows)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("mode", choices=("run", "apply"))
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=11)
    p.add_argument("--rates", default="1000,2000,4000,8000,16000,32000,64000")
    p.add_argument("--seconds", type=float, default=5.0)
    args, extra = p.parse_known_args(argv)
    path = sweep_file(args.workload)
    if args.mode == "run":
        cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", args.workload,
               "--seed", str(args.seed), "--seconds", str(args.seconds), "--trace", "0",
               "--sweep", args.rates, *extra]
        r = subprocess.run(cmd, cwd=CHECKOUT, capture_output=True, text=True)
        sys.stderr.write(r.stderr[-8000:])
        if r.returncode != 0:
            return r.returncode
        steps = json.loads(r.stdout.strip().splitlines()[-1])
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump({"workload": args.workload, "seed": args.seed, "seconds": args.seconds,
                       "steps": steps}, f, indent=1)
        print(table(steps))
        return 0
    with open(path) as f:
        steps = json.load(f)["steps"]
    bench = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    k = knee(steps, P99_LIMIT_MS)
    if k is None:
        k = knee(steps)
        print(f"no rate met p99 <= {P99_LIMIT_MS} ms; the knee is the backlog limit alone")
    if k is None:
        print("no rate kept up")
        return 1
    tpath = os.path.join(BENCH_DIR, "traffic", f"{cell['traffic']}.json")
    with open(tpath) as f:
        traffic = json.load(f)
    traffic["rate_per_s"] = int(round(SHARE * k))
    with open(tpath, "w") as f:
        json.dump(traffic, f, indent=2)
        f.write("\n")
    print(table(steps))
    print(f"knee {k:g}/s; {tpath} rate_per_s = {traffic['rate_per_s']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
