"""End-to-end driver (deliverable b): meta-train the paper's FEMNIST CNN
with FedMeta for a few hundred rounds, with periodic evaluation,
checkpointing, communication accounting, and a FedAvg baseline run on
the same client split through the experiment plane — the full
Figure-2-style experiment at CPU scale.

  PYTHONPATH=src python examples/femnist_fedmeta.py --rounds 300 \
      --algo meta-sgd --ckpt /tmp/fedmeta_femnist
"""
import argparse
import json

import jax

from repro.checkpoint import save_server_state
from repro.core import classification_loss, make_algorithm
from repro.data import make_femnist
from repro.federated.experiment import (comm_to_target, default_plan,
                                        make_trainer)
from repro.federated.server import FederatedTrainer, evaluate_meta, \
    evaluate_global
from repro.models.paper import femnist_cnn
from repro.optim import adam


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=300)
    ap.add_argument("--algo", default="maml",
                    choices=["maml", "fomaml", "meta-sgd", "reptile"])
    ap.add_argument("--clients", type=int, default=100)
    ap.add_argument("--clients-per-round", type=int, default=4)
    ap.add_argument("--support-frac", type=float, default=0.2)
    ap.add_argument("--inner-lr", type=float, default=0.01)
    ap.add_argument("--outer-lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="/tmp/fedmeta_femnist")
    ap.add_argument("--eval-every", type=int, default=50)
    ap.add_argument("--packed", action="store_true",
                    help="run FedMeta on the flat pipeline (client plane)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="skip the FedAvg baseline comparison")
    args = ap.parse_args()

    ds = make_femnist(num_clients=args.clients, mean_samples=60, seed=0)
    train, val, test = ds.split_clients(seed=0)
    print("dataset:", json.dumps(ds.stats()))

    model = femnist_cnn(num_classes=62, hidden=128)
    loss_fn, eval_fn = classification_loss(model.apply)
    algo = make_algorithm(args.algo, loss_fn, eval_fn,
                          inner_lr=args.inner_lr)
    trainer = FederatedTrainer(algo, adam(args.outer_lr), train,
                               clients_per_round=args.clients_per_round,
                               support_frac=args.support_frac,
                               support_size=16, query_size=16,
                               packed=args.packed)
    state = trainer.init(jax.random.PRNGKey(0), model.init)
    flops = trainer.measure_flops(state)
    print(f"client procedure: {flops/1e9:.2f} GFLOPs / client / round")

    for start in range(0, args.rounds, args.eval_every):
        n = min(args.eval_every, args.rounds - start)
        state = trainer.run(state, n)
        # phi_tree() — NOT state["phi"] — so the packed pipeline (flat φ
        # buffer) evaluates identically to the tree pipeline
        acc, _, _ = evaluate_meta(algo, trainer.phi_tree(state), val,
                                  support_frac=args.support_frac,
                                  support_size=16, query_size=16,
                                  evaluator=trainer.evaluator())
        trainer.history[-1]["eval_acc"] = acc
        path = save_server_state(args.ckpt, start + n, state)
        print(f"round {start+n:4d}  val_acc={acc:.4f}  "
              f"{trainer.comm.summary()}  ckpt={path}")

    test_acc, per_client, _ = evaluate_meta(algo, trainer.phi_tree(state),
                                            test,
                                            support_frac=args.support_frac,
                                            support_size=16, query_size=16,
                                            evaluator=trainer.evaluator())
    print(f"FINAL: FedMeta({args.algo}) test acc = {test_acc:.4f} "
          f"(min client {per_client.min():.3f}, "
          f"max {per_client.max():.3f})")

    if args.no_baseline:
        return

    # FedAvg baseline on the SAME split/stream via the experiment plane
    plan = default_plan("femnist", rounds=args.rounds,
                        eval_every=args.eval_every, num_clients=args.clients,
                        clients_per_round=args.clients_per_round,
                        support_frac=args.support_frac)
    fa = make_trainer(plan, "fedavg", loss_fn, eval_fn, train)
    fa_state = fa.init(jax.random.PRNGKey(0), model.init)
    fa.measure_flops(fa_state)
    fa_state = fa.run(fa_state, args.rounds, eval_every=args.eval_every,
                      eval_clients=val)
    fa_acc, _, _ = evaluate_global(eval_fn, fa_state["theta"], test,
                                   support_frac=args.support_frac,
                                   support_size=16, query_size=16,
                                   evaluator=fa.evaluator())
    print(f"BASELINE: FedAvg test acc = {fa_acc:.4f}  {fa.comm.summary()}")
    target = min(acc, max((r.get("eval_acc") or 0.0) for r in fa.history))
    fmt = lambda row: f"{row['comm_MB']:.2f}MB@r{row['rounds']}" if row \
        else "not reached"  # noqa: E731
    print(f"comm to target_acc={target:.4f}: "
          f"FedMeta={fmt(comm_to_target(trainer.history, target))} "
          f"FedAvg={fmt(comm_to_target(fa.history, target))}")


if __name__ == "__main__":
    main()
