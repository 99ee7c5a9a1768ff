"""Readings that the limits of a training cell are set from.

    python3 bench/calibrate.py --workload smollm-360m.train4k \
        --seeds 101,102,103 --controls 3

On the chip, at the cell's own size, in one process. For each seed the
cell's set-up drives the program's step through the checked rounds, and
the plain reference follows the same rounds; the gaps between the two
are the program's (lower) readings. On the first `--controls` seeds it
also reads, against the same reference:

  control  the reference computed in the next precision below the one
           the configuration states
  half     the reference on half of each round's clients, the mean
           taken over them (the fault "half of the batch left out")

Each driver's `follow(ctx, session, variant)` computes these.

A state left unchanged reads 1 on update_norm_gap and grad_norm_gap by
construction and needs no run. Prints one JSON line per seed and
reading, and a summary; the limits file gets the numbers by hand.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent / "src"))

from benchlib import chips, compare, spec, trace  # noqa: E402

import run as bench_run  # noqa: E402

NO_LIMITS = {"loss_gap": 0.0, "grad_norm_gap": 0.0, "update_norm_gap": 0.0}


def gaps(got: dict, want: dict) -> dict:
    return {c["name"]: c["value"]
            for c in compare.training_checks(got, want, NO_LIMITS)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    cell = spec.Cell(args.workload)
    chips.enable_compile_cache()
    devs = chips.require_chips(cell.chips)
    ref_mod = cell.reference()
    driver = cell.driver()
    cache: dict = {}
    rows = []
    for i, seed in enumerate(seeds):
        ctx = bench_run.RunContext(
            workload=cell.name, config=cell.config, traffic=cell.traffic,
            reference=ref_mod, limits=NO_LIMITS, seed=seed, seconds=0.0,
            devices=list(devs), t0=time.perf_counter(),
            tracer=trace.Tracer(None), quiet=True)
        t = time.perf_counter()
        s = driver.Session(ctx)
        setup_s = time.perf_counter() - t
        prog = s.readings
        s.close()
        t = time.perf_counter()
        want = driver.follow(ctx, s, "reference", cache)
        ref_s = time.perf_counter() - t
        row = {"seed": seed, "program": gaps(prog, want),
               "setup_s": setup_s, "reference_s": ref_s,
               "losses": {"program": prog["losses"],
                          "reference": want["losses"]}}
        if i < args.controls:
            row["control"] = gaps(driver.follow(ctx, s, "control", cache),
                                  want)
            row["half"] = gaps(driver.follow(ctx, s, "half", cache), want)
        rows.append(row)
        print(json.dumps(row), flush=True)

    summary = {
        name: {"program_max": max(r["program"][name] for r in rows),
               "control_min": min((r["control"][name] for r in rows
                                   if "control" in r), default=None),
               "half_min": min((r["half"][name] for r in rows
                                if "half" in r), default=None)}
        for name in NO_LIMITS}
    print(json.dumps({"workload": cell.name, "seeds": seeds,
                      "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
