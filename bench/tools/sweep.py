"""Find the highest arrival rate a serve cell sustains: one process, one
engine (weights from the first seed), a window at each offered rate on
each seed.  Not run by the benchmark's own runs; its readings set the rate
a mix file fixes.

    python3 bench/tools/sweep.py --workload <cell> --seconds 50 \
        --seeds 1 2 3 --rates 1.2 1.4 1.6

The backlog is the number of requests that have arrived and not finished,
sampled every half second.  A rate is sustained on a seed where the
backlog's mean over the window's last quarter exceeds its mean over the
second quarter by less than one request: a queue that grows adds a
request every few seconds above capacity, while at a rate the engine keeps
up with the two means differ only by the noise of the arrivals.  The
sustained rate is the highest one that, with every lower rate of the
sweep, is sustained on every seed.  One JSON line
per seed and rate, then a summary line.
"""
import argparse
import copy
import json
import sys
import time
from pathlib import Path

import numpy as np

_ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness  # noqa: E402

SAMPLE_S = 0.5


def backlog(served, t: float) -> int:
    """Requests that arrived by ``t`` and had not finished by then."""
    return sum(1 for s in served if s.arrival_s <= t and not (
        s.req.done and s.times and s.times[-1] <= t))


def quarter_means(served, seconds: float) -> tuple:
    """The backlog's mean over the window's second and last quarters."""
    def mean(lo, hi):
        ts = np.arange(lo, hi, SAMPLE_S)
        return float(np.mean([backlog(served, t) for t in ts]))
    return mean(seconds / 4, seconds / 2), mean(3 * seconds / 4, seconds)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import serving, traffic

    cell = harness.find_cell(harness.load_json(_ROOT / "BENCHMARK.json"),
                             args.workload)
    harness.enable_compile_cache()
    clock = harness.CompileClock()
    device = harness.device_info(cell.chips)
    peak = harness.peak_of(device["kind"])
    engine = serving.build_engine(cell.config, args.seeds[0])
    serving.warm_up(engine, cell.config, cell.mix)
    held = {rate: True for rate in args.rates}
    for seed in args.seeds:
        for rate in args.rates:
            mix = copy.deepcopy(cell.mix)
            mix["arrival"]["rate_per_s"] = rate
            ctx = harness.RunContext(cell, seed, args.seconds, False,
                                     time.perf_counter(), clock, peak, 1)
            reqs = traffic.generate(mix, seed, cell.config["vocab_size"],
                                    args.seconds)
            window = serving.run_window(ctx, engine, reqs)
            served = [s for s in window.served if s.arrival_s < args.seconds]
            second, last = quarter_means(served, args.seconds)
            sustained = last - second < 1.0
            held[rate] = held[rate] and sustained
            serving.drain_first_tokens(engine, window)
            ttft = [s.times[0] - s.arrival_s for s in served if s.times]
            e2e = serving.end_to_end(window)
            print(json.dumps({
                "seed": seed, "rate": rate, "arrived": len(served),
                "finished": sum(s.req.done for s in served),
                "backlog_q2_mean": second, "backlog_q4_mean": last,
                "backlog_end": backlog(served, args.seconds),
                "sustained": sustained,
                "ttft_p50_ms": traffic.percentile(ttft, 50) * 1e3,
                "ttft_p90_ms": traffic.percentile(ttft, 90) * 1e3,
                "output_tokens_per_s": e2e["output_tokens_per_s"],
                "itl_p95_ms": e2e.get("itl_p95_ms"),
                "window_compiles": ctx.window_compiles}), flush=True)
            engine.queue.clear()
            engine.run_until_done()
    highest = None
    for rate in sorted(args.rates):
        if not held[rate]:
            break
        highest = rate
    print(json.dumps({"sustained_on_every_seed":
                      [r for r in args.rates if held[r]],
                      "highest": highest}), flush=True)


if __name__ == "__main__":
    main()
