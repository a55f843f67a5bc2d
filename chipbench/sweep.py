#!/usr/bin/env python3
"""The offered-rate sweep that finds an open-loop cell's knee.

    python3 chipbench/sweep.py --workload <chat cell> --rates 2,3,4 \
        --seconds 20 --seed <n> [--out FILE]

One process and one engine; for each rate, the cell's mix at that rate
(lead-in, window, drain), and one JSON line: arrivals, requests finished,
time to first token and inter-token tails, and the mean queue of waiting
requests in each quarter of the window. The knee is the highest rate at
which the queue does not grow over the window; the cell's mix file takes
0.8 of it as its rate. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run, spec  # noqa: E402
from chipbench.driver import Driver, Record, log  # noqa: E402


def sweep(root, workload, rates, seconds, seed, *, bench_dir=spec.HERE,
          require_tpu=True):
    cell = spec.load_cell(root, workload, bench_dir)
    run.use_cache(root)
    if require_tpu:
        run.devices(cell.chips)
    ref = spec.reference(cell.config["reference"], bench_dir)
    weights = run.make_weights(ref, cell.config, seed)
    drv = Driver(cell, weights, seed, bench_dir=bench_dir)
    drv.warm_up()
    for rate in rates:
        cell.traffic["rate_rps"] = rate
        drv.rec = Record(cell=cell, arch=drv.arch, fmt_map=drv.fmt_map,
                         seconds=0.0)
        drv.run_open(seconds)
        rec = drv.rec
        steps = rec.window_steps()
        quarters = [[s.queue for s in steps
                     if rec.t_open + q * seconds / 4 <= s.t1
                     < rec.t_open + (q + 1) * seconds / 4] for q in range(4)]
        out = {"workload": workload, "rate_rps": rate,
               "arrivals": len(rec.measured()),
               "finished": sum(r.tokens is not None for r in rec.measured()),
               "queue_by_quarter": [float(np.mean(q)) if q else 0.0
                                    for q in quarters],
               "lanes_busy": float(np.mean([s.lanes for s in steps]))
               if steps else 0.0}
        for name in ("ttft_p90_ms", "itl_p95_ms", "admit_ms"):
            out[name] = spec.metric_reader(name, bench_dir)(rec)
        out.update(drv.lateness())
        yield out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    for line in sweep(ROOT, args.workload, rates, args.seconds, args.seed):
        text = json.dumps(line)
        log(text)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")


if __name__ == "__main__":
    main()
