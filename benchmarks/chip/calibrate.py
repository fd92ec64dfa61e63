#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from; not run by the
benchmark's own runs.

  python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \
      --seeds 11,12,13

For each seed, in one process: a normal run of the cell (its result line
gives the program's widest gap, ``max_logit_gap``), and, on the same
sampled requests, the control: the plain reference one precision step
lower (float8 e4m3 for the dense family's bfloat16), through the tenant's
model family's ``control_gaps``. The control's reading is
the widest gap, under the float32 reference, of the token the control puts
first at each position of the same prompts and served tokens; it goes
through the same decision as the run's own reading (``harness.judge``,
against the configuration's limit), and has to come out not correct.
Prints one ``calibrate`` line a seed.
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


def control_reading(config, seed, chosen) -> dict:
    worst, n, misses = 0.0, 0, 0
    for i, t in enumerate(config["tenants"]):
        mine = [tr for tr in chosen if tr.item.tenant == t["name"]]
        if not mine:
            continue
        fam = harness.tenant_family(t)
        w = fam.make(t["arch"], seed, i)
        for tr in mine:
            g = fam.control_gaps(w, t["arch"], tr.item.prompt, tr.served())
            worst = max(worst, float(g.max()))
            n += len(g)
            misses += int((g > 0).sum())
        del w
        gc.collect()
    limit = config["limits"]["max_logit_gap"]
    return {"control_max_logit_gap": worst, "control_tokens": n,
            "control_not_argmax": misses, "limit": limit,
            "control_correct": harness.judge(
                {"max_logit_gap": worst, "tokens": n}, limit)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args()
    compare = harness.compare
    for seed in [int(s) for s in a.seeds.split(",")]:
        got = {}

        def both(config, s, chosen):
            out = compare(config, s, chosen)
            got.update(out)
            got.update(control_reading(config, s, chosen))
            return out

        harness.compare = both
        rc = harness.run(["--workload", a.workload, "--seed", str(seed),
                          "--seconds", str(a.seconds), "--trace", "0"],
                         t_process=T_PROCESS)
        print("calibrate " + json.dumps(dict(got, seed=seed, rc=rc)),
              flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
