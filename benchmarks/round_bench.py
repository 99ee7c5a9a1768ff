"""End-to-end round benchmark: tree vs packed vs packed+client-plane.

The meta-step bench (``meta_step_bench.py``) timed the *server* half of
the pipeline introduced in PR 1; this bench times the unit the paper
actually iterates — one full FedMeta round (m clients' ModelTraining
inner loop + aggregation + outer Adam) — across

  pipeline:    "tree"         — per-leaf everything (seed path)
               "packed"       — PR 1: flat server half (fused (m, N)
                                aggregation + single-pass flat Adam),
                                tree client inner loop
               "packed_plane" — this PR: the client inner loop also runs
                                on flat memory — chunks of clients adapt
                                in lockstep on a (C, N) plane with the
                                fused inner-update kernel, per-client
                                meta-gradients come out flat
  client_axis: "vmap", "scan", "chunked@k", "sharded" (shard_map over a
               mesh built from every visible device; 1 device on a plain
               CPU host — pass --devices N, matched to the physical core
               count, to see real client parallelism)
  scale:       two model scales; "large" is a deep narrow stack (the
               many-leaf regime where per-leaf dispatch dominates and
               the flat plane pays off most)

recording interleaved-min wall time plus XLA cost/memory analysis per
row (same caveat as the meta-step bench: scan bodies are counted once).

The headline summary number is
``round_speedup_client_plane_vs_packed`` — this PR's full client plane
(fused inner loop + shardable client axis) vs the PR 1 packed pipeline
as it shipped (client axis pinned to one device), best configuration
each, at the larger scale, measured at round granularity. Same-axis
ratios are also recorded for transparency. The second-order algorithms
(maml/meta-sgd order 2) are correct through the client plane but pay a
flat↔tree conversion penalty in reverse-over-reverse mode on CPU — use
them with client_plane=False there (no automatic fallback); see
DESIGN.md §9.

The second half of the bench (``async``) times the round DRIVER, not
just the jitted step: a full `FederatedTrainer.run` over a synthetic
client pool with LEAF-scale local datasets, where each round's host
half (numpy task sampling + staging) costs a real fraction of the
device half. Variants: the PR 3 synchronous loop (prefetch_depth=0,
per-round float() metrics readback) vs the async engine at
prefetch_depth∈{1,2} (deferred metrics, flush at exit) vs fused-K
(lax.scan round blocks). Headline: ``async_speedup`` — sync wall over
the best pipelined wall, at the large scale (DESIGN.md §12). The loop
math is bit-identical across variants (tests/test_async_engine.py), so
this is pure overlap/dispatch win.

The third section (``population``) measures the PR 7 claim directly:
a femnist population served lazily from an independent-mode
`ClientRegistry` (O(1) per-client seeding, bounded LRU cache) through
the population-plane trainer (over-selection + deadline + worker pool),
at 10^3 / 10^4 / 10^5 clients. Each size runs in its OWN subprocess so
``ru_maxrss`` — which is monotone within a process — is a true
per-size peak; the recorded ``peak_rss_mb`` staying flat across three
decades of population is the bounded-memory evidence, and
``rounds_per_s`` shows round throughput is population-size independent.
``--population-only`` re-runs just this section and MERGES it into an
existing BENCH_round.json without touching the other sections' numbers.

Usage:
  PYTHONPATH=src python benchmarks/round_bench.py            # full
  PYTHONPATH=src python benchmarks/round_bench.py --dry-run  # CI smoke
  PYTHONPATH=src python benchmarks/round_bench.py --population-only
Emits results/bench/BENCH_round.json (see --out).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.meta_step_bench import _analyze, _build_task, \
    _time_interleaved

# deep narrow stacks: many leaves, modest per-leaf FLOPs — the regime
# where the inner loop is dispatch-bound and the client plane collapses
# per-client per-leaf op soup into one fused pass per inner step
SCALES = {
    "small": dict(layers=8, width=32, in_dim=16),
    "large": dict(layers=48, width=32, in_dim=16),
    "tiny": dict(layers=3, width=16, in_dim=8),       # --dry-run only
}
INNER_STEPS = 3
CLIENTS = 16

# driver-level async bench: a client pool with LEAF-scale local data so
# host-side sampling (support/query split copies the client's full
# local arrays) is a realistic fraction of the round — the overlap the
# async engine exists to reclaim
ASYNC_SCALES = {
    "large": dict(model="large", pool=256, client_samples=8192, m=16,
                  batch=64, rounds=16, warmup=8, fuse=8),
    "tiny": dict(model="tiny", pool=16, client_samples=64, m=4,
                 batch=8, rounds=4, warmup=2, fuse=2),    # --dry-run
}
ASYNC_VARIANTS = (
    # PR 3 synchronous driver: inline sampling, per-round float() sync
    ("sync", dict()),
    ("prefetch1", dict(prefetch_depth=1, flush_every=0)),
    ("prefetch2", dict(prefetch_depth=2, flush_every=0)),
    # fused-K: lax.scan over K-round blocks staged as one buffer
    ("fused", dict(prefetch_depth=2, flush_every=0)),     # + fuse_rounds
)


def _bench_async(scale_key: str, reps: int):
    """Wall time per round of the full driver loop, per engine variant.

    Every variant replays the identical seeded run (bit-identical
    history — tests/test_async_engine.py), so wall deltas are pure
    pipelining. Warmup rounds compile the per-round step and, for the
    fused variant, the K-round scan block (`warmup` is a multiple of
    K so the timed region never compiles)."""
    import jax

    from repro.data.federated import ClientData, TaskStream
    from repro.federated.server import FederatedTrainer
    from repro.optim import adam

    cfg = ASYNC_SCALES[scale_key]
    algo, model_init, *_ = _build_task(
        SCALES[cfg["model"]], cfg["m"], cfg["batch"], algo_name="fomaml",
        inner_steps=INNER_STEPS)
    rng = np.random.RandomState(0)
    D = SCALES[cfg["model"]]["in_dim"]
    clients = [
        ClientData(rng.normal(0, 1, (cfg["client_samples"], D))
                   .astype(np.float32),
                   rng.normal(0, 1, (cfg["client_samples"], D))
                   .astype(np.float32))
        for _ in range(cfg["pool"])]

    stream = TaskStream(clients, cfg["m"], 0.5, cfg["batch"], cfg["batch"],
                        np.random.RandomState(0))
    t0 = time.perf_counter()
    for _ in range(max(2, cfg["warmup"])):
        stream.next()
    sample_ms = (time.perf_counter() - t0) / max(2, cfg["warmup"]) * 1e3

    rows = []
    for name, knobs in ASYNC_VARIANTS:
        if name == "fused":
            knobs = dict(knobs, fuse_rounds=cfg["fuse"])
        tr = FederatedTrainer(
            algo, adam(1e-3), clients, cfg["m"], support_frac=0.5,
            support_size=cfg["batch"], query_size=cfg["batch"], seed=0,
            packed=True, **knobs)
        state = tr.init(jax.random.PRNGKey(0), model_init)
        state = tr.run(state, cfg["warmup"])
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            state = tr.run(state, cfg["rounds"])
            walls.append((time.perf_counter() - t0) / cfg["rounds"])
        rows.append({"scale": scale_key, "variant": name,
                     "wall_ms_per_round": float(np.min(walls) * 1e3),
                     "rounds_timed": cfg["rounds"] * reps,
                     "sample_ms": sample_ms, **knobs})
        print(f"round.async.{scale_key}.{name},"
              f"{rows[-1]['wall_ms_per_round'] * 1e3:.0f},"
              f"sample_ms={sample_ms:.2f}", flush=True)
    return rows


# bytes-on-the-wire section (DESIGN.md §17): same driver bench, upload
# codec axis. Each variant reruns the identical seeded driver loop with
# a different wire format; rows record the measured wall per round AND
# the codec-true per-client upload bytes, so the summary shows the
# compression multiplier compounding (bf16 2×, int8 ~4×, top-5% bf16
# values ~13× vs dense f32).
COMM_VARIANTS = (
    ("f32", {}),
    ("bf16", dict(block_dtype="bfloat16")),
    ("int8+ef", dict(codec="int8")),
    ("topk0.05+ef", dict(codec="topk", block_dtype="bfloat16")),
)


def _bench_comm(scale_key: str, reps: int):
    """Wall time per round + true upload bytes per client, per codec."""
    import jax
    import jax.numpy as jnp

    from repro.data.federated import ClientData
    from repro.federated.server import FederatedTrainer
    from repro.kernels.meta_update.compress import CompressionConfig
    from repro.optim import adam

    cfg = ASYNC_SCALES[scale_key]
    algo, model_init, *_ = _build_task(
        SCALES[cfg["model"]], cfg["m"], cfg["batch"], algo_name="fomaml",
        inner_steps=INNER_STEPS)
    rng = np.random.RandomState(0)
    D = SCALES[cfg["model"]]["in_dim"]
    clients = [
        ClientData(rng.normal(0, 1, (cfg["client_samples"], D))
                   .astype(np.float32),
                   rng.normal(0, 1, (cfg["client_samples"], D))
                   .astype(np.float32))
        for _ in range(cfg["pool"])]

    rows = []
    for name, knobs in COMM_VARIANTS:
        kw = {}
        if knobs.get("block_dtype"):
            kw["block_dtype"] = jnp.dtype(knobs["block_dtype"])
        if knobs.get("codec"):
            kw["compression"] = CompressionConfig(
                knobs["codec"], topk_frac=0.05)
        tr = FederatedTrainer(
            algo, adam(1e-3), clients, cfg["m"], support_frac=0.5,
            support_size=cfg["batch"], query_size=cfg["batch"], seed=0,
            packed=True, **kw)
        state = tr.init(jax.random.PRNGKey(0), model_init)
        state = tr.run(state, cfg["warmup"])
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            state = tr.run(state, cfg["rounds"])
            walls.append((time.perf_counter() - t0) / cfg["rounds"])
        per_client = (tr.comm.grad_bytes if tr.comm.grad_bytes is not None
                      else tr.comm.phi_bytes)
        rows.append({"scale": scale_key, "variant": name,
                     "codec": tr.comm.codec,
                     "wall_ms_per_round": float(np.min(walls) * 1e3),
                     "upload_bytes_per_client": int(per_client),
                     "phi_bytes": int(tr.comm.phi_bytes),
                     "rounds_timed": cfg["rounds"] * reps})
        print(f"round.comm.{scale_key}.{name},"
              f"{rows[-1]['wall_ms_per_round'] * 1e3:.0f},"
              f"upload_B={per_client}", flush=True)
    return rows


def _summarize_comm(comm_rows):
    if not comm_rows:
        return {}
    base = next((r for r in comm_rows if r["variant"] == "f32"), None)
    if base is None:
        return {}
    out = {"upload_bytes_per_client": {
        r["variant"]: r["upload_bytes_per_client"] for r in comm_rows}}
    for r in comm_rows:
        if r["variant"] != "f32":
            out[f"upload_multiplier_{r['variant']}"] = round(
                base["upload_bytes_per_client"]
                / r["upload_bytes_per_client"], 2)
            out[f"wall_overhead_{r['variant']}"] = round(
                r["wall_ms_per_round"] / base["wall_ms_per_round"], 3)
    return {"comm": out}


POPULATION_SIZES = (1_000, 10_000, 100_000)
POPULATION_SIZES_DRY = (200, 1_000)


def _population_child(n_clients: int, rounds: int, cache: int) -> dict:
    """One population size, measured in THIS process (spawned as a
    subprocess so ru_maxrss is a per-size peak): 20-round femnist
    population-plane run off the independent-mode lazy registry."""
    import resource

    import jax

    from repro.core import classification_loss, make_algorithm
    from repro.federated.experiment import DATASETS
    from repro.federated.population import UnreliabilityConfig
    from repro.federated.server import FederatedTrainer
    from repro.optim import adam

    su = DATASETS["femnist"]
    reg = su["data"](n_clients, 0, lazy=True, independent=True,
                     cache_clients=cache)
    train, _, _ = reg.split_clients(seed=0)
    model = su["model"]()
    algo = make_algorithm("fomaml", *classification_loss(model.apply),
                          inner_lr=0.05)
    tr = FederatedTrainer(
        algo, adam(1e-3), train, 8, support_frac=0.2, support_size=16,
        query_size=16, seed=0, packed=True,
        unreliability=UnreliabilityConfig(fail_rate=0.2, seed=0),
        over_select=0.5, round_deadline=1.6, pool_workers=2)
    state = tr.init(jax.random.PRNGKey(0), model.init)
    state = tr.run(state, 2)              # compile outside the timing
    t0 = time.perf_counter()
    tr.run(state, rounds)
    wall = time.perf_counter() - t0
    return {
        "clients": n_clients, "rounds": rounds,
        "rounds_per_s": rounds / wall,
        "wall_s": wall,
        "peak_rss_mb": resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss / 1024.0,
        "arrived_total": tr.history[-1]["arrived_total"],
        "selected_total": tr.history[-1]["selected_total"],
        "cache": reg.cache_stats(),
    }


def bench_population(dry: bool):
    """One subprocess per population size (fresh ru_maxrss each) ->
    the per-size rows. Call it before this process touches the device:
    on a TPU each child needs the chip for itself."""
    sizes = POPULATION_SIZES_DRY if dry else POPULATION_SIZES
    rounds, cache = (3, 32) if dry else (20, 64)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (env.get("PYTHONPATH", ""),
                    os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__)))) if p)
    rows = []
    for n in sizes:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--population-child", str(n), "--population-rounds",
             str(rounds), "--population-cache", str(cache)],
            capture_output=True, text=True, env=env, check=False)
        if proc.returncode != 0:
            raise RuntimeError(
                f"population child (n={n}) failed:\n{proc.stderr[-2000:]}")
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        rows.append(row)
        print(f"round.population.{n},rounds_per_s="
              f"{row['rounds_per_s']:.2f},peak_rss_mb="
              f"{row['peak_rss_mb']:.0f},peak_resident="
              f"{row['cache']['peak_resident']}", flush=True)
    return rows


def _summarize_population(pop_rows):
    if not pop_rows:
        return {}
    lo, hi = pop_rows[0], pop_rows[-1]
    return {"population": {
        "max_clients": hi["clients"],
        "rounds_per_s_at_max": hi["rounds_per_s"],
        "peak_rss_mb_at_max": hi["peak_rss_mb"],
        # bounded-memory evidence: RSS growth across the population
        # decades (≈1.0 = resident set independent of fleet size)
        "rss_growth_vs_smallest": hi["peak_rss_mb"] / lo["peak_rss_mb"],
        "cache_peak_resident": hi["cache"]["peak_resident"],
    }}


def run(*, dry: bool = False, reps: int = 10, algo_name: str = "fomaml",
        json_out: str = "results/bench/BENCH_round.json", pop_rows=None):
    """All sections -> the report. `pop_rows` takes the population
    section from a caller that ran `bench_population` before touching
    the device; without it that section runs first."""
    import jax

    from repro.core.fedmeta import (init_packed_state, make_meta_train_step,
                                    make_packed_meta_train_step)
    from repro.optim import adam
    from repro.sharding.context import make_mesh
    from repro.utils.flat import plane_for
    from repro.utils.pytree import tree_size

    if pop_rows is None:
        pop_rows = bench_population(dry)

    scales = ["tiny"] if dry else ["small", "large"]
    m = 4 if dry else CLIENTS
    batch = 8
    reps = 1 if dry else reps
    axes = [("vmap", None), ("sharded", None)] if dry else \
        [("vmap", None), ("scan", None), ("chunked", 4), ("sharded", None)]

    n_dev = jax.device_count()
    mesh = make_mesh((n_dev,), ("clients",))

    rows = []
    for scale in scales:
        algo, model_init, sup, qry, weights = _build_task(
            SCALES[scale], m, batch, algo_name=algo_name,
            inner_steps=INNER_STEPS)
        opt = adam(1e-3)
        phi = algo.init_state(jax.random.PRNGKey(0), model_init)
        plane = plane_for(phi)
        n_params = tree_size(phi)

        configs = []
        for pipeline in ("tree", "packed", "packed_plane"):
            for axis, chunk in axes:
                if pipeline == "tree":
                    step = make_meta_train_step(
                        algo, opt, client_axis=axis, client_chunk=chunk,
                        mesh=mesh, donate=False)
                    state = {"phi": phi, "opt": opt.init(phi)}
                else:
                    step = make_packed_meta_train_step(
                        algo, opt, plane, client_axis=axis,
                        client_chunk=chunk, impl="xla",
                        client_plane=(pipeline == "packed_plane"),
                        mesh=mesh, donate=False)
                    state = init_packed_state(opt, plane, phi)
                configs.append({
                    "step": step, "state": state,
                    "args": (sup, qry, weights),
                    "row": {"scale": scale, "pipeline": pipeline,
                            "client_axis": axis, "client_chunk": chunk,
                            "clients": m, "inner_steps": INNER_STEPS,
                            "algo": algo.name, "devices": n_dev,
                            "n_params": int(n_params),
                            "n_padded": int(plane.n_padded)},
                })
        walls = _time_interleaved(configs, reps)
        for c in configs:
            analysis, _ = _analyze(c["step"], c["state"], *c["args"])
            wall_us, wall_med = walls[id(c)]
            row = {**c["row"], "wall_us_per_round": wall_us,
                   "wall_us_median": wall_med, **analysis}
            rows.append(row)
            chunk_tag = (f"@{row['client_chunk']}"
                         if row["client_chunk"] else "")
            print(f"round.{scale}.{row['pipeline']}."
                  f"{row['client_axis']}{chunk_tag},{wall_us:.0f},"
                  f"temp={analysis['temp_bytes']}", flush=True)

    async_rows = _bench_async("tiny" if dry else "large",
                              reps=1 if dry else 2)
    comm_rows = _bench_comm("tiny" if dry else "large",
                            reps=1 if dry else 2)

    report = {
        "bench": "round",
        "backend": jax.default_backend(),
        "devices": n_dev,
        "dry_run": dry,
        "reps": reps,
        "rows": rows,
        "async_rows": async_rows,
        "comm_rows": comm_rows,
        "population_rows": pop_rows,
        "summary": {**_summarize(rows, async_rows),
                    **_summarize_comm(comm_rows),
                    **_summarize_population(pop_rows)},
    }
    os.makedirs(os.path.dirname(json_out) or ".", exist_ok=True)
    with open(json_out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {json_out}", flush=True)
    return report


def run_population_only(*, dry: bool = False, json_out: str):
    """Run just the population section and merge it into an existing
    report (the other sections' committed numbers are left untouched)."""
    return _run_section_only("population_rows", bench_population(dry),
                             _summarize_population, dry=dry,
                             json_out=json_out)


def run_comm_only(*, dry: bool = False, json_out: str):
    """Run just the bytes-on-the-wire section (§17) and merge it into an
    existing report, population-only style."""
    rows = _bench_comm("tiny" if dry else "large", reps=1 if dry else 2)
    return _run_section_only("comm_rows", rows, _summarize_comm,
                             dry=dry, json_out=json_out)


def _run_section_only(key, rows, summarize, *, dry, json_out):
    report = {"bench": "round", "dry_run": dry, "summary": {}}
    if os.path.exists(json_out):
        with open(json_out) as f:
            report = json.load(f)
    report[key] = rows
    report.setdefault("summary", {}).update(summarize(rows))
    os.makedirs(os.path.dirname(json_out) or ".", exist_ok=True)
    with open(json_out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {json_out} ({key} section merged)", flush=True)
    return report


def _summarize_async(async_rows):
    """sync driver wall vs the best pipelined variant, same seed, same
    (bit-identical) math — the measured overlap win."""
    sync = next((r for r in async_rows if r["variant"] == "sync"), None)
    piped = [r for r in async_rows if r["variant"] != "sync"]
    if not (sync and piped):
        return {}
    best = min(piped, key=lambda r: r["wall_ms_per_round"])
    out = {
        "async_speedup": sync["wall_ms_per_round"] / best["wall_ms_per_round"],
        "async_headline": {
            "sync_wall_ms": sync["wall_ms_per_round"],
            "best_variant": best["variant"],
            "best_wall_ms": best["wall_ms_per_round"],
            "host_sample_ms": sync["sample_ms"],
        },
    }
    for r in piped:
        out[f"async_speedup_{r['variant']}"] = (
            sync["wall_ms_per_round"] / r["wall_ms_per_round"])
    return out


def _summarize(rows, async_rows=()):
    out = {}
    scales = {r["scale"] for r in rows}
    big = "large" if "large" in scales else sorted(scales)[-1]
    out["largest_scale"] = big

    def pick(pipeline, axis):
        for r in rows:
            if (r["scale"] == big and r["pipeline"] == pipeline
                    and r["client_axis"] == axis):
                return r
        return None

    def best(pipeline, axes):
        cand = [pick(pipeline, a) for a in axes]
        cand = [r for r in cand if r]
        return min(cand, key=lambda r: r["wall_us_per_round"]) \
            if cand else None

    # headline: this PR's full client plane (fused inner loop + the
    # shardable client axis) vs the PR 1 packed pipeline as it shipped
    # (client axis pinned to one device: vmap/scan/chunked only), best
    # configuration each, at the larger scale — round granularity
    pr1 = best("packed", ("vmap", "scan", "chunked"))
    plane = best("packed_plane", ("vmap", "scan", "chunked", "sharded"))
    if pr1 and plane:
        out["round_speedup_client_plane_vs_packed"] = (
            pr1["wall_us_per_round"] / plane["wall_us_per_round"])
        out["headline"] = {
            "pr1_packed_best": f"{pr1['pipeline']}/{pr1['client_axis']}",
            "client_plane_best":
                f"{plane['pipeline']}/{plane['client_axis']}",
            "wall_us_pr1": pr1["wall_us_per_round"],
            "wall_us_client_plane": plane["wall_us_per_round"],
        }

    # transparency: same-axis ratios, including the sharded axis applied
    # to the PR 1 pipeline (the sharded axis alone, without the fused
    # inner loop, is also new in this PR)
    for axis in ("vmap", "scan", "chunked", "sharded"):
        pk, pl_ = pick("packed", axis), pick("packed_plane", axis)
        if pk and pl_:
            out[f"round_speedup_client_plane_vs_packed_{axis}"] = (
                pk["wall_us_per_round"] / pl_["wall_us_per_round"])

    # and vs the seed default (tree/vmap), for the trajectory
    tree_v = pick("tree", "vmap")
    if tree_v and plane:
        out["round_speedup_client_plane_vs_tree_vmap"] = (
            tree_v["wall_us_per_round"] / plane["wall_us_per_round"])
    out.update(_summarize_async(async_rows))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny scale, 1 rep — CI smoke")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--algo", default="fomaml")
    ap.add_argument("--population-only", action="store_true",
                    help="run just the population-scaling section and "
                         "merge it into the existing --out report")
    ap.add_argument("--comm-only", action="store_true",
                    help="run just the bytes-on-the-wire (codec) "
                         "section and merge it into the existing --out "
                         "report")
    ap.add_argument("--population-child", type=int, default=0,
                    help=argparse.SUPPRESS)   # internal: subprocess mode
    ap.add_argument("--population-rounds", type=int, default=20,
                    help=argparse.SUPPRESS)
    ap.add_argument("--population-cache", type=int, default=64,
                    help=argparse.SUPPRESS)
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host CPU devices (sets XLA_FLAGS; must "
                         "run before jax is imported — match the "
                         "physical core count for a fair sharded row)")
    ap.add_argument("--out", default=None,
                    help="output JSON (default: the committed artifact "
                         "for full runs, the gitignored smoke/ dir for "
                         "--dry-run so a doc-following smoke cannot "
                         "clobber the full-run numbers)")
    args = ap.parse_args()
    if args.out is None:
        args.out = ("results/bench/smoke/BENCH_round.json" if args.dry_run
                    else "results/bench/BENCH_round.json")
    if args.population_child:
        print(json.dumps(_population_child(
            args.population_child, args.population_rounds,
            args.population_cache)), flush=True)
        return
    if args.population_only:
        run_population_only(dry=args.dry_run, json_out=args.out)
        return
    if args.comm_only:
        run_comm_only(dry=args.dry_run, json_out=args.out)
        return
    if args.devices:
        import os
        import sys
        if "jax" in sys.modules:
            raise RuntimeError("--devices must be set before jax import; "
                               "run round_bench.py standalone")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")
    run(dry=args.dry_run, reps=args.reps, algo_name=args.algo,
        json_out=args.out)


if __name__ == "__main__":
    main()
