"""Record the small chip traces the reducer tests read.

    python3 bench/tests/record_trace_fixture.py <out_dir>

Runs each cell's driver at a test's size, traced, on the chip this
process holds, and copies each trace under 1 MB to
`<out_dir>/<workload>.xplane.pb` with the driver's `work` record beside
it (`<workload>.work.json`); the files go under `bench/tests/fixtures/`.
An LM step's trace is larger even at a test's size (its operations'
names carry every shape) and is left out.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import tiny  # noqa: E402
from benchlib import chips, trace  # noqa: E402

import run as bench_run  # noqa: E402


def main(out_dir: str) -> int:
    devs = chips.require_chips(1)
    chips.enable_compile_cache()
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    for name in tiny.CELLS:
        cell = tiny.tiny_cell(name)
        res, _ = bench_run.run_cell(cell, devs, seed=7, seconds=0.0,
                                    traced=True, t0=time.perf_counter(),
                                    limits=tiny.LOOSE)
        src = trace.find_xplane(str(bench_run.OUT_DIR / f"trace-{name}"))
        print(name, Path(src).stat().st_size, "bytes", flush=True)
        if Path(src).stat().st_size >= 1 << 20:
            continue
        shutil.copy(src, out / f"{name}.xplane.pb")
        (out / f"{name}.work.json").write_text(json.dumps(res["work"]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
