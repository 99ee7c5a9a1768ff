"""The paper's headline experiment (Fig. 3 / §4): FedMeta vs FedAvg on a
shared client split, sampling stream, and communication budget.

Runs FedMeta (MAML / FOMAML / Meta-SGD, optionally Reptile) against
FedAvg and FedAvg(Meta) through the experiment plane
(`repro.federated.experiment`), records per-round comm/accuracy curves,
and prints the comm-to-target-accuracy table. JSON artifacts land under
``results/experiments/``.

  PYTHONPATH=src python examples/compare_fedmeta_fedavg.py \
      --datasets femnist,sent140 --rounds 60 --eval-every 5

  # CI smoke (few rounds, tiny client pools, both datasets):
  PYTHONPATH=src python examples/compare_fedmeta_fedavg.py --dry-run
"""
import argparse

from repro.federated.experiment import (DEFAULT_METHODS, default_plan,
                                        format_table, run_comparison)
from repro.federated.faults import FaultConfig
from repro.federated.privacy import DPConfig
from repro.kernels.meta_update.compress import CompressionConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--datasets", default="femnist,sent140")
    ap.add_argument("--methods", default=",".join(DEFAULT_METHODS))
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--clients", type=int, default=0,
                    help="override registry client-pool size")
    ap.add_argument("--support-frac", type=float, default=None,
                    help="override the per-dataset registry default")
    ap.add_argument("--local-steps", type=int, default=3)
    ap.add_argument("--target-acc", type=float, default=None,
                    help="fixed target accuracy (default: highest "
                         "accuracy every method reaches)")
    ap.add_argument("--pipeline", default="tree",
                    choices=["tree", "client_plane"])
    ap.add_argument("--client-chunk", type=int, default=0)
    ap.add_argument("--prefetch-depth", type=int, default=0,
                    help="async round engine: staged round blocks ahead "
                         "of the device (0 = synchronous loop; history "
                         "is bit-identical either way)")
    ap.add_argument("--flush-every", type=int, default=1,
                    help="deferred-metrics drain cadence (0 = at exit)")
    ap.add_argument("--fuse-rounds", type=int, default=1,
                    help="lax.scan round-block size (client_plane "
                         "pipeline)")
    ap.add_argument("--aggregator", default="mean",
                    choices=["mean", "masked_mean", "screen", "trimmed"],
                    help="FedMeta (m, N) aggregation mode (DESIGN.md "
                         "§14; non-mean needs pipeline client_plane)")
    ap.add_argument("--fault-dropout", type=float, default=0.0,
                    help="fraction of each round's clients whose update "
                         "never arrives (fault injection)")
    ap.add_argument("--fault-byzantine", type=float, default=0.0,
                    help="fraction of Byzantine (sign-flip) clients")
    ap.add_argument("--fault-nonfinite", type=float, default=0.0,
                    help="fraction of clients uploading NaN gradients")
    ap.add_argument("--lazy-population", action="store_true",
                    help="serve clients from the lazy ClientRegistry "
                         "(sequential mode: bit-identical to eager)")
    ap.add_argument("--cache-clients", type=int, default=0,
                    help="LRU cap on resident lazy clients (0 = "
                         "unbounded)")
    ap.add_argument("--over-select", type=float, default=0.0,
                    help="sample m·(1+x) candidates per round, "
                         "aggregate the first m arrivals (FedMeta "
                         "methods; needs pipeline client_plane)")
    ap.add_argument("--round-deadline", type=float, default=0.0,
                    help="arrival latency cutoff, in unreliability "
                         "units (0 = no deadline)")
    ap.add_argument("--unreliable-fail-rate", type=float, default=0.0,
                    help="per-(client, round) transient failure "
                         "probability of the arrival model")
    ap.add_argument("--pool-workers", type=int, default=0,
                    help="shard-materializing worker threads "
                         "(0 = inline)")
    ap.add_argument("--eval-clients-cap", type=int, default=0,
                    help="cap on val/test eval cohort size (large lazy "
                         "populations)")
    ap.add_argument("--codec", default="",
                    choices=["", "int8", "topk"],
                    help="FedMeta upload compression (DESIGN.md §17; "
                         "needs pipeline client_plane)")
    ap.add_argument("--topk-frac", type=float, default=0.05,
                    help="fraction of real parameters each client "
                         "transmits under --codec topk")
    ap.add_argument("--no-error-feedback", action="store_true",
                    help="disable the per-client EF residual state")
    ap.add_argument("--block-dtype", default="",
                    help="packed gradient-block wire dtype (e.g. "
                         "bfloat16; also the top-k value dtype)")
    ap.add_argument("--opt-state-dtype", default="",
                    help="fused-Adam m/v state dtype (e.g. bfloat16 — "
                         "dequantized in-kernel)")
    ap.add_argument("--dp-clip-norm", type=float, default=0.0,
                    help="central-DP per-client L2 clip (0 = off)")
    ap.add_argument("--dp-noise-multiplier", type=float, default=0.0,
                    help="central-DP noise multiplier z (σ = z·S/m)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--outdir", default="results/experiments")
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny rounds/pools for CI smoke")
    args = ap.parse_args()

    over = dict(methods=tuple(args.methods.split(",")), rounds=args.rounds,
                eval_every=args.eval_every,
                local_steps=args.local_steps, target_acc=args.target_acc,
                pipeline=args.pipeline,
                client_chunk=args.client_chunk or None, seed=args.seed,
                prefetch_depth=args.prefetch_depth,
                flush_every=args.flush_every, fuse_rounds=args.fuse_rounds)
    if args.aggregator != "mean":
        over["aggregator"] = args.aggregator
    if args.fault_dropout or args.fault_byzantine or args.fault_nonfinite:
        over["faults"] = FaultConfig(dropout=args.fault_dropout,
                                     byzantine=args.fault_byzantine,
                                     nonfinite=args.fault_nonfinite)
    if args.lazy_population:
        over.update(lazy_population=True,
                    cache_clients=args.cache_clients or None)
    if args.over_select:
        over["over_select"] = args.over_select
    if args.round_deadline:
        over["round_deadline"] = args.round_deadline
    if args.unreliable_fail_rate:
        from repro.federated.population import UnreliabilityConfig
        over["unreliability"] = UnreliabilityConfig(
            fail_rate=args.unreliable_fail_rate, seed=args.seed)
    if args.pool_workers:
        over["pool_workers"] = args.pool_workers
    if args.codec:
        over["compression"] = CompressionConfig(
            args.codec, topk_frac=args.topk_frac,
            error_feedback=not args.no_error_feedback)
    if args.block_dtype:
        over["block_dtype"] = args.block_dtype
    if args.opt_state_dtype:
        over["opt_state_dtype"] = args.opt_state_dtype
    if args.dp_clip_norm:
        over["dp"] = DPConfig(clip_norm=args.dp_clip_norm,
                              noise_multiplier=args.dp_noise_multiplier,
                              seed=args.seed)
    if args.eval_clients_cap:
        over["eval_clients_cap"] = args.eval_clients_cap
    if args.clients:
        over["num_clients"] = args.clients
    if args.support_frac is not None:
        over["support_frac"] = args.support_frac
    if args.dry_run:
        # smoke names + smoke outdir (unless overridden): a dry run must
        # not overwrite the committed full-run artifacts under
        # results/experiments/
        over.update(rounds=4, eval_every=2, num_clients=24)
        if args.outdir == "results/experiments":
            args.outdir = "results/experiments-smoke"

    for dataset in args.datasets.split(","):
        plan = default_plan(
            dataset, **over,
            **({"name": f"{dataset}_smoke"} if args.dry_run else {}))
        out = run_comparison(plan, out_dir=args.outdir, log=print)
        print(f"\n=== {dataset} (pipeline={plan.pipeline}, "
              f"rounds={plan.rounds}) ===")
        print(format_table(out))
        print()


if __name__ == "__main__":
    main()
