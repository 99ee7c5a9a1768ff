"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines per benchmark plus the
per-table result lines emitted by each module.

  (default) reduced rounds so the suite finishes on 1 CPU core
  --full   paper-scale rounds (hours on CPU)
  --only   comma-separated subset:
           kernels,meta_step,round,table2,fig3,table3,fairness

All artifacts go under --outdir (default results/bench/) — nothing is
written at the repo root.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np


def _bench_kernels():
    """Microbench the three Pallas kernel oracles (wall time on CPU; TPU
    numbers come from the roofline analysis, not from here)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.attention.ops import flash_attention
    from repro.kernels.meta_update.ops import meta_update
    from repro.kernels.ssd.ops import ssd_chunked

    rng = np.random.RandomState(0)
    rows = []

    q = jnp.asarray(rng.normal(0, 1, (1, 512, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (1, 512, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (1, 512, 2, 64)), jnp.float32)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, impl="xla"))
    f(q, k, v).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        f(q, k, v).block_until_ready()
    rows.append(("kernel.attention.xla", (time.perf_counter() - t0) / 10 * 1e6,
                 "B1xL512xH4"))

    x = jnp.asarray(rng.normal(0, 1, (1, 256, 4, 16)), jnp.float32)
    dt = jnp.asarray(np.ones((1, 256, 4)) * 0.1, jnp.float32)
    A = jnp.asarray(-np.ones(4), jnp.float32)
    Bm = jnp.asarray(rng.normal(0, 1, (1, 256, 32)), jnp.float32)
    Cm = jnp.asarray(rng.normal(0, 1, (1, 256, 32)), jnp.float32)
    g = jax.jit(lambda *a: ssd_chunked(*a, chunk=64, impl="xla"))
    g(x, dt, A, Bm, Cm).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        g(x, dt, A, Bm, Cm).block_until_ready()
    rows.append(("kernel.ssd.xla", (time.perf_counter() - t0) / 10 * 1e6,
                 "L256xh4"))

    theta = {"w": jnp.zeros((1 << 20,), jnp.float32)}
    grads = {"w": jnp.ones((1 << 20,), jnp.float32)}
    h = jax.jit(lambda t, g: meta_update(t, 0.01, g, impl="xla"))
    h(theta, grads)["w"].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        h(theta, grads)["w"].block_until_ready()
    rows.append(("kernel.meta_update.xla",
                 (time.perf_counter() - t0) / 10 * 1e6, "1M params"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only",
                    default="kernels,meta_step,round,experiment,table2,fig3,"
                            "table3,fairness")
    ap.add_argument("--rounds", type=int, default=0)
    ap.add_argument("--outdir", default="results/bench")
    args = ap.parse_args()
    os.makedirs(args.outdir, exist_ok=True)
    # reduced (non---full) runs write ONLY under this gitignored smoke
    # dir — a doc-following smoke run can never shadow (or accidentally
    # get committed next to) a real artifact
    smoke_dir = os.path.join(args.outdir, "smoke")
    if not args.full:
        os.makedirs(smoke_dir, exist_ok=True)
    only = set(args.only.split(","))
    rounds = args.rounds or (400 if args.full else 120)

    print("name,us_per_call,derived", flush=True)
    pop_rows = None
    if "round" in only:
        # the round bench's population children each need the device
        # for themselves, so they run before this process touches it
        from benchmarks import round_bench
        pop_rows = round_bench.bench_population(dry=not args.full)

    if "kernels" in only:
        for name, us, derived in _bench_kernels():
            print(f"{name},{us:.1f},{derived}", flush=True)

    if "meta_step" in only:
        from benchmarks import meta_step_bench
        t0 = time.time()
        # the committed perf-trajectory artifact lives in outdir; a
        # reduced run writes into the gitignored smoke/ subdir so it
        # cannot clobber the full-run numbers
        out = (os.path.join(args.outdir, "BENCH_meta_step.json")
               if args.full
               else os.path.join(smoke_dir, "BENCH_meta_step.json"))
        report = meta_step_bench.run(dry=not args.full, json_out=out)
        spd = report["summary"].get("wall_speedup_packed_vs_tree_vmap")
        print(f"meta_step,{(time.time()-t0)*1e6:.0f},"
              f"packed_speedup={f'{spd:.2f}x' if spd else 'n/a'}", flush=True)

    if "round" in only:
        from benchmarks import round_bench
        t0 = time.time()
        out = (os.path.join(args.outdir, "BENCH_round.json")
               if args.full
               else os.path.join(smoke_dir, "BENCH_round.json"))
        report = round_bench.run(dry=not args.full, json_out=out,
                                 pop_rows=pop_rows)
        spd = report["summary"].get("round_speedup_client_plane_vs_packed")
        aspd = report["summary"].get("async_speedup")
        print(f"round,{(time.time()-t0)*1e6:.0f},"
              f"client_plane_speedup={f'{spd:.2f}x' if spd else 'n/a'},"
              f"async_speedup={f'{aspd:.2f}x' if aspd else 'n/a'}",
              flush=True)

    if "experiment" in only:
        from benchmarks import experiment_bench
        t0 = time.time()
        # smoke summary goes into the gitignored smoke/ dir — must not
        # clobber the committed full-run numbers (same guard as the
        # other benches) — and ALL artifacts stay under --outdir (the
        # committed results/experiments/ refresh goes through
        # experiment_bench / examples/compare_fedmeta_fedavg.py
        # directly)
        out = (os.path.join(args.outdir, "experiment_summary.json")
               if args.full
               else os.path.join(smoke_dir, "experiment_summary.json"))
        summary = experiment_bench.run(
            dry=not args.full, json_out=out,
            out_dir=(os.path.join(args.outdir, "experiments")
                     if args.full
                     else os.path.join(smoke_dir, "experiments")))
        # headline = best FEDMETA reduction; fedavg(meta) is a baseline.
        # ">=x" strings mark lower bounds and survive into the headline.
        reds = [v for s in summary.values()
                for m, v in s["comm_reduction_vs_fedavg"].items()
                if v and m not in ("fedavg", "fedavg(meta)")]
        best = max(reds, key=lambda v: float(str(v).lstrip(">="))) \
            if reds else "n/a"
        print(f"experiment,{(time.time()-t0)*1e6:.0f},"
              f"max_comm_reduction={best}", flush=True)

    if "table2" in only:
        from benchmarks import table2_leaf
        datasets = ("femnist", "shakespeare", "sent140")
        fracs = (0.2, 0.5, 0.9) if args.full else (0.2,)
        for dsname in datasets:
            t0 = time.time()
            rows = table2_leaf.run(
                dsname, rounds=rounds, support_fracs=fracs,
                json_out=os.path.join(args.outdir, f"table2_{dsname}.json"))
            best = max(rows, key=lambda r: r["test_acc"])
            print(f"table2.{dsname},{(time.time()-t0)*1e6/max(rounds,1):.0f},"
                  f"best={best['method']}@{best['test_acc']:.3f}", flush=True)

    if "fig3" in only:
        from benchmarks import fig3_overhead
        t0 = time.time()
        rows = fig3_overhead.run(
            "sent140", target_acc=0.70, max_rounds=rounds * 2,
            json_out=os.path.join(args.outdir, "fig3_sent140.json"))
        red = [r.get("comm_reduction_vs_fedavg") for r in rows
               if r["method"] in ("maml", "meta-sgd")
               and r.get("comm_reduction_vs_fedavg")]
        print(f"fig3.sent140,{(time.time()-t0)*1e6:.0f},"
              f"comm_reduction={max(red) if red else 'n/a'}", flush=True)

    if "table3" in only:
        from benchmarks import table3_production
        t0 = time.time()
        rows = table3_production.run(
            rounds=rounds,
            json_out=os.path.join(args.outdir, "table3.json"))
        best = max(rows.items(), key=lambda kv: kv[1]["top1"])
        print(f"table3,{(time.time()-t0)*1e6:.0f},"
              f"best={best[0]}@top1={best[1]['top1']:.3f}", flush=True)

    if "fairness" in only:
        from benchmarks import fairness
        t0 = time.time()
        rows = fairness.run(
            "femnist", rounds=rounds,
            json_out=os.path.join(args.outdir, "fairness.json"))
        print(f"fairness.femnist,{(time.time()-t0)*1e6:.0f},"
              f"std_fedavg={rows['fedavg']['std']:.3f}_maml="
              f"{rows['maml']['std']:.3f}", flush=True)


if __name__ == "__main__":
    main()
