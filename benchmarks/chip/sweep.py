#!/usr/bin/env python3
"""Offered-load sweep of one cell, to find the rate it sustains; not run
by the benchmark's own runs.

  python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
      --seconds <s> --rates 0.2,0.3,0.4

One process serves the cell's mix once at each rate, in the order given,
exactly as a run does but without the comparison with the reference, and
prints one ``sweep`` line a rate: tokens per second, the tails, the
queue wait, the most KV pages in use and the requests due and finished in
the window. The knee is where tokens per second stops rising with the
rate while the queue wait grows.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    a = ap.parse_args()
    end_to_end = harness.end_to_end
    queue_wait = harness.load_reader("queue_wait_p95_s")
    for rate in [float(r) for r in a.rates.split(",")]:
        got = {}

        def keep(rec, t_open, t_close):
            e2e = end_to_end(rec, t_open, t_close)
            lo, hi = t_open, t_close
            got.update(
                tokens_per_s=e2e["out_tokens"] / e2e["window_s"],
                ttft_p95_s=harness.p95(e2e["ttft"]),
                tbt_p95_s=harness.p95(e2e["gaps"]),
                queue_wait_p95_s=queue_wait(
                    harness.Readings(rec, e2e, None, {}, 0, None)),
                kv_pages_max=rec.kv_used_max, engine_steps=rec.steps,
                due=len(e2e["ttft"]),
                finished=sum(1 for tr in rec.tracks.values()
                             if tr.done and tr.tokens
                             and lo <= tr.tokens[-1] <= hi))
            return e2e

        harness.end_to_end = keep
        harness.compare = lambda config, seed, chosen: {
            "max_logit_gap": float("nan"), "tokens": 0,
            "requests": 0, "not_argmax": 0}
        rc = harness.run(
            ["--workload", a.workload, "--seed", str(a.seed),
             "--seconds", str(a.seconds), "--trace", "0"],
            t_process=T_PROCESS,
            scale=lambda config, mix: (config, dict(mix, rate_rps=rate)))
        print("sweep " + json.dumps(dict(got, rate_rps=rate, rc=rc)),
              flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
