"""Uniform cohorts: round t takes the first C of a permutation of the K
clients keyed by (seed, t); client k weighs n_k / n and runs all H local
steps."""
import jax
import numpy as np

KEYS = set()


def program(spec, ctx):
    from repro.core import ClientPopulation, DeviceUniformSampler

    return {"trainer": {"sampler": DeviceUniformSampler(
        ClientPopulation(counts=np.asarray(ctx.counts)),
        ctx.clients_per_round, seed=ctx.seeds.cohort)}}


def reference(spec, ctx):
    counts = np.asarray(ctx.counts)
    weights = (counts / counts.sum()).astype(np.float32)
    C, H = ctx.clients_per_round, ctx.local_steps

    def cohort(t):
        key = jax.random.fold_in(jax.random.PRNGKey(ctx.seeds.cohort), t)
        ids = np.asarray(jax.random.permutation(key, len(counts))[:C])
        return ids, weights[ids], np.full(C, H, np.int32)

    return cohort
