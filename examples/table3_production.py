"""The paper's production recommendation scenario (§4.3 / Table 3)
through the scenario plane: FedMeta's small LOCAL-head recommender vs
FedAvg's GLOBAL-service classifier, on one shared client split and
sampling stream, with per-method θ-size communication accounting and
fairness (per-client accuracy distribution) blocks in the artifact.

The paper's point is a size asymmetry: a production service has a huge
catalogue (2,400 services; 2,420-way unified classifier), but each
client only ever uses a handful (2–36), so FedMeta can ship a model
whose head covers just the client's own services (40-way) — fewer bytes
per round AND a better-conditioned per-client problem. The scenario
plane makes both halves measurable: `CommTracker` charges each method
its own θ bytes, and the comm-to-target table reports bytes — not
rounds — to the shared target.

  PYTHONPATH=src python examples/table3_production.py --rounds 60

  # CI smoke (few rounds, tiny pools):
  PYTHONPATH=src python examples/table3_production.py --dry-run

The non-federated Table-3 baselines (MFU/MRU/NB/LR-self/NN-self and
the unified fine-tuned NN) have no entry point in this repo.
"""
import argparse

from repro.federated.experiment import (DEFAULT_METHODS, default_plan,
                                        format_table, run_comparison)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--methods", default=",".join(DEFAULT_METHODS))
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--eval-every", type=int, default=2)
    ap.add_argument("--clients", type=int, default=0,
                    help="override registry client-pool size")
    ap.add_argument("--local-head", type=int, default=0,
                    help="override the FedMeta head width (registry: 40)")
    ap.add_argument("--target-acc", type=float, default=None,
                    help="fixed target accuracy (default: highest "
                         "accuracy every method sustainably reaches)")
    ap.add_argument("--pipeline", default="tree",
                    choices=["tree", "client_plane"])
    ap.add_argument("--prefetch-depth", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--outdir", default="results/experiments")
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny rounds/pools for CI smoke")
    args = ap.parse_args()

    over = dict(methods=tuple(args.methods.split(",")), rounds=args.rounds,
                eval_every=args.eval_every, target_acc=args.target_acc,
                pipeline=args.pipeline, prefetch_depth=args.prefetch_depth,
                seed=args.seed)
    if args.clients:
        over["num_clients"] = args.clients
    if args.local_head:
        over["local_head"] = args.local_head
    if args.dry_run:
        # smoke name + smoke outdir (unless overridden): a dry run must
        # not overwrite — or sit next to — the committed full-run
        # recommend_compare.json
        over.update(rounds=4, eval_every=2, num_clients=24,
                    name="recommend_smoke")
        if args.outdir == "results/experiments":
            args.outdir = "results/experiments-smoke"

    plan = default_plan("recommend", **over)
    out = run_comparison(plan, out_dir=args.outdir, log=print)

    print(f"\n=== recommend (local_head={plan.local_head}, "
          f"rounds={plan.rounds}) ===")
    print(format_table(out))
    print("\nper-method model size + fairness (per-client accuracy "
          "distribution at final eval):")
    for m, res in out["methods"].items():
        f, c = res["fairness"], res["comm"]
        print(f"  {m:<14} phi_MB={c['phi_MB']:.4f}  mean={f['mean']:.4f}  "
              f"var={f['variance']:.5f}  worst10%={f['worst10_mean']:.4f}  "
              f"p10={f['deciles'][0]:.4f}  p90={f['deciles'][-1]:.4f}")


if __name__ == "__main__":
    main()
