"""End-to-end driver: federated pre-training of a ~100M-parameter dense
transformer (qwen3-family block structure) on synthetic non-IID token
streams, with FedMom on the server and SGD on clients.

The model is built by the same assembly that serves the 10 assigned
architectures; on a TPU pod the identical script scales to the full configs
via --arch and the production mesh (see repro/launch/dryrun.py for the
lowering proof).  CPU default below trains a reduced number of rounds.

    PYTHONPATH=src python examples/federated_llm.py --rounds 30      # smoke
    PYTHONPATH=src python examples/federated_llm.py --rounds 300     # full
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import RoundConfig, UniformSampler, fedmom
from repro.data.federated import FederatedDataset, lm_clients_to_dataset
from repro.data.synthetic import synthetic_token_clients
from repro.launch.train import FederatedTrainer
from repro.models import transformer as T
from repro.models.config import ModelConfig


def model_100m() -> ModelConfig:
    return ModelConfig(
        name="fed-llm-100m", family="dense",
        n_layers=12, d_model=640, n_heads=10, n_kv_heads=5, d_head=64,
        d_ff=2560, vocab=8192, qk_norm=True, act="swiglu",
        dtype="float32", remat=False, scan_layers=True,
        source="qwen3-family block structure, scaled to ~100M")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--m", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--arch", default=None,
                    help="train a reduced assigned arch instead")
    args = ap.parse_args()

    cfg = (get_config(args.arch).reduced().replace(dtype="float32")
           if args.arch else model_100m())
    params, _ = T.init(cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model {cfg.name}: {n_params/1e6:.1f}M params")

    streams = synthetic_token_clients(args.clients, cfg.vocab,
                                      tokens_per_client=20_000, seed=0)
    ds = lm_clients_to_dataset(streams, args.seq, seed=1)
    pop = ds.population()

    opt = fedmom(eta=pop.n_clients / args.m, beta=0.9)
    rcfg = RoundConfig(clients_per_round=args.m,
                       local_steps=args.local_steps, lr=args.lr,
                       placement="mesh", compute_dtype="float32")

    def loss_fn(p, batch):
        return T.loss_fn(p, cfg, batch)

    trainer = FederatedTrainer(
        loss_fn=loss_fn, server_opt=opt, rcfg=rcfg, dataset=ds,
        sampler=UniformSampler(pop, args.m, seed=2),
        state=opt.init(params),
        ckpt_path="results/fed_llm_ckpt.npz", ckpt_every=100,
        local_batch=args.batch)
    t0 = time.time()
    hist = trainer.run(args.rounds, log_every=max(args.rounds // 10, 1))
    print(f"done: {args.rounds} rounds in {time.time()-t0:.0f}s; "
          f"loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
