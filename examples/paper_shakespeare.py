"""Paper task 2: character-level LSTM (1x128, Kim et al. 2016) on synthetic
Shakespeare with M=2 active clients — §5.1/§5.4 of the paper.  Compares
FedSGD (H=1), FedAvg and FedMom in rounds-to-loss.

    PYTHONPATH=src python examples/paper_shakespeare.py [--rounds 120]
"""
import argparse

import jax
import numpy as np

from repro.core import RoundConfig, UniformSampler, fedavg, fedmom
from repro.data import synthetic_shakespeare
from repro.data.federated import FederatedDataset, lm_clients_to_dataset
from repro.data.synthetic import SHAKESPEARE_SEQ
from repro.launch.train import FederatedTrainer
from repro.models import small


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=120)
    ap.add_argument("--clients", type=int, default=40)
    ap.add_argument("--lr", type=float, default=0.8)
    args = ap.parse_args()

    streams, counts = synthetic_shakespeare(n_clients=args.clients, seed=0)
    ds = lm_clients_to_dataset([c["text"] for c in streams],
                               SHAKESPEARE_SEQ, seed=1)
    pop = ds.population()
    K, M = pop.n_clients, 2
    w0 = small.lstm_init(jax.random.PRNGKey(0))

    runs = [
        ("FedSGD", fedavg(eta=K / M), 1),
        ("FedAvg", fedavg(eta=K / M), 10),
        ("FedMom", fedmom(eta=K / M, beta=0.9), 10),
    ]
    final = {}
    for name, opt, H in runs:
        print(f"\n=== {name} (H={H}) ===")
        rcfg = RoundConfig(clients_per_round=M, local_steps=H, lr=args.lr,
                           placement="mesh", compute_dtype="float32")
        trainer = FederatedTrainer(
            loss_fn=small.lstm_loss, server_opt=opt, rcfg=rcfg,
            dataset=ds, sampler=UniformSampler(pop, M, seed=2),
            state=opt.init(w0), local_batch=10)
        hist = trainer.run(args.rounds, log_every=30)
        final[name] = hist[-1]["loss"]
    print("\nrounds-to-loss summary (lower = faster):",
          {k: round(v, 4) for k, v in final.items()})


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
