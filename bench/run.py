"""FedMeta chip benchmark: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout that holds `BENCHMARK.json`. The run
loads the cell's configuration and traffic files by name, turns on the
persistent compile cache at the program's fixed path, makes the weights
on the device from the seed, warms up the cell's own shapes, measures
for `--seconds`, checks what the timed path produced against the plain
reference beside the configuration, and prints one JSON line.

With `--trace 0` the line's metrics are the cell's end-to-end metrics;
with `--trace 1` the profiler records the window and the line carries
the per-layer metrics, the device's busy and window seconds, and a
breakdown. Each number compared with the reference is printed beside
its limit, as the last lines on standard error and as the line's last
key. Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result line.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent / "src"))

from benchlib import chips, compare, spec, trace  # noqa: E402

OUT_DIR = BENCH_DIR / "out"


@dataclasses.dataclass
class RunContext:
    """What a driver gets: the cell's files, the seed, the chips."""
    workload: str
    config: dict
    traffic: dict
    reference: object
    limits: dict
    seed: int
    seconds: float
    devices: list
    t0: float
    tracer: trace.Tracer

    quiet: bool = False

    def memory_peak(self) -> int:
        return chips.memory_peak_bytes(self.devices)

    def log(self, what: str) -> None:
        if not self.quiet:
            print(f"bench: {time.perf_counter() - self.t0:9.3f} s  {what}",
                  file=sys.stderr, flush=True)


def limits_for(workload: str, bench_dir: Path = BENCH_DIR) -> dict:
    return spec.load_json(bench_dir / "limits" / f"{workload}.json")[
        "limits"]


def per_layer_metrics(cell: spec.Cell, summary, work: dict,
                      device_kind: str) -> dict:
    """Each per-layer metric's reader on the traced window; a reader
    that finds nothing to read returns None and the metric is left
    out."""
    peaks = chips.peaks_for(device_kind)
    readers = cell.readers()
    out = {}
    for entry in cell.per_layer():
        value = readers[entry["name"]].read(summary, work, peaks)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def result_line(cell: spec.Cell, res: dict, devs, traced: bool,
                summary=None) -> dict:
    info = chips.device_info(devs)
    device = {"platform": info["platform"], "kind": info["kind"],
              "count": info["count"],
              "memory_peak_bytes": max(res["memory"].values()),
              **{f"memory_{k}": v for k, v in res["memory"].items()}}
    line = {"workload": cell.name,
            "correct": compare.passed(res["checks"]) and not res["failed"],
            "attempted": res["attempted"], "failed": res["failed"]}
    if traced:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["metrics"] = per_layer_metrics(cell, summary, res["work"],
                                            info["kind"])
        line["breakdown"] = trace.breakdown(summary)
    else:
        values = dict(res["end_to_end"], setup_s=res["setup_s"])
        line["metrics"] = {m["name"]: {"value": values[m["name"]],
                                       "unit": m["unit"]}
                           for m in cell.end_to_end()}
    line["device"] = device
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in res["checks"]}
    return line


def run_cell(cell: spec.Cell, devs, *, seed: int, seconds: float,
             traced: bool, t0: float = T0, limits: dict | None = None):
    """Drive one run; -> (driver result, trace summary or None)."""
    tdir = str(OUT_DIR / f"trace-{cell.name}") if traced else None
    ctx = RunContext(
        workload=cell.name, config=cell.config, traffic=cell.traffic,
        reference=cell.reference(),
        limits=limits if limits is not None else limits_for(cell.name),
        seed=seed, seconds=seconds, devices=list(devs), t0=t0,
        tracer=trace.Tracer(tdir))
    res = cell.driver().run(ctx)
    summary = trace.load(tdir) if traced else None
    ctx.log("done")
    return res, summary


def print_checks(checks: list[dict]) -> None:
    for c in checks:
        where = f" (worst leaf {c['leaf']})" if c.get("leaf") else ""
        ok = math.isfinite(c["value"]) and c["value"] <= c["limit"]
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAILED'}{where}", file=sys.stderr,
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.Cell(args.workload)
    cache = chips.enable_compile_cache()
    try:
        devs = chips.require_chips(cell.chips)
    except chips.NoChip as e:
        print(f"bench: {args.workload}: {e}", file=sys.stderr)
        return 2
    info = chips.device_info(devs)
    print(f"bench: {args.workload} on {info['kind']} x{info['count']} "
          f"({info['platform']}); compile cache {cache}", file=sys.stderr,
          flush=True)
    res, summary = run_cell(cell, devs, seed=args.seed,
                            seconds=args.seconds, traced=bool(args.trace))
    line = result_line(cell, res, devs, bool(args.trace), summary)
    print(f"bench: readings {json.dumps(res.get('readings'))}",
          file=sys.stderr, flush=True)
    print_checks(res["checks"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
