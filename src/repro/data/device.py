"""Device-resident federated data plane (Data plane v1).

For the corpora the paper benchmarks (LEAF-scale FEMNIST / Shakespeare, à la
McMahan et al. 2017) the *whole* federated dataset fits on device, so round
data never needs to cross the host boundary: ``DeviceFederatedDataset`` packs
the corpus once into padded ``[K, n_max, ...]`` arrays (one leaf per field,
dtypes preserved, each sample one flat row) and ``gather_round_batch``
materializes a round's ``[C, H, b, ...]`` batch stack *inside* the compiled
computation — sampling indices with the same ``(seed, t, client_id)``-keyed
draw the host ``FederatedDataset.round_batches`` uses
(``minibatch_indices``), which makes the two gathers bit-equal and keeps
every driver tier on one trajectory.

Memory ceiling: packing costs ``K * n_max * itemsize`` per field — the
*maximum* client size times the client count, not the corpus size — so it is
the right plane when client sizes are bounded (paper Table 2: FEMNIST
n_max ~ a few hundred 28x28 images => tens of MB for K in the hundreds).
That is a resident cost: a round reads just the ``C * H * b`` rows it
draws, whatever ``n_max`` is.  On the TPU the chunk program may also hold
a row-major copy of a leaf, made once per chunk: the device keeps a packed
leaf in its least-padded layout, which can put the client or the row axis
minor-most.  ``nbytes`` does not count that copy.
For corpora past device memory, use the shard-cached streaming plane
(``plan="streaming"``) or the host prefetch-queue plane (``plan="scanned"``);
``nbytes`` reports the packed footprint, which is what ``plan="auto"``
compares against the memory budget to decide.

The class is a pytree, so it is passed to jitted chunk functions as a plain
argument (no baked-in constants; the XLA executable is reusable across
datasets of the same shape).  When a mesh + axis-rules context is active
(``sharding/rules.py``), ``pack`` shards the client axis over the mesh's
('pod','data') axes — each data shard holds its own clients' corpus, the
same placement the round engine uses for per-client model replicas.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sampling import ClientPopulation
from repro.data.federated import (FederatedDataset, minibatch_indices,
                                  validate_client_data)
from repro.sharding import rules as sharding_rules


@jax.tree_util.register_pytree_node_class
class DeviceFederatedDataset:
    """Whole federated corpus as padded device arrays.

    ``arrays``: dict of ``[K, n_max]`` or ``[K, n_max, F]`` leaves (client
    k's samples in rows [0, n_k), zero padding above; a sample with dims
    is one flat row of ``F`` elements); ``sample_shapes``: each
    field's per-sample shape, which the gather restores; ``counts``:
    ``[K]`` int32 n_k; ``seed`` keys the minibatch draws exactly like
    ``FederatedDataset``.
    """

    def __init__(self, arrays: Dict[str, jax.Array], counts: jax.Array,
                 sample_shapes: Dict[str, tuple], seed: int = 0):
        self.arrays = arrays
        self.counts = counts
        self.sample_shapes = sample_shapes
        self.seed = seed

    # -- pytree protocol (jit-arg friendly) -----------------------------
    def tree_flatten(self):
        keys = tuple(sorted(self.arrays))
        children = tuple(self.arrays[k] for k in keys) + (self.counts,)
        shapes = tuple(self.sample_shapes[k] for k in keys)
        return children, (keys, self.seed, shapes)

    @classmethod
    def tree_unflatten(cls, aux, children):
        keys, seed, shapes = aux
        *leaves, counts = children
        return cls(dict(zip(keys, leaves)), counts,
                   dict(zip(keys, shapes)), seed)

    # -- construction ---------------------------------------------------
    @classmethod
    def pack(cls, data: List[Dict[str, np.ndarray]], seed: int = 0,
             shard_clients: bool = True) -> "DeviceFederatedDataset":
        """Pack per-client dicts into padded device arrays.

        Dtype-aware: each field keeps its own dtype (int32 token streams
        next to float32 images).  A sample of two or more dims is stored
        flat (``[K, n_max, 784]`` for 28x28x1 images).  The TPU lays a
        ``[K, n_max, 28, 28, 1]`` leaf out with the client axis minor-most,
        and the row gather then relayouts the whole corpus inside every
        round; from a flat leaf the compiler relayouts once, outside the
        round loop, together with the matmul-precision cast.

        With ``shard_clients`` and an active mesh context, leaves are
        placed with the 'clients' logical axis sharded over the mesh
        (replicated otherwise) — under
        ``ExecutionPlan(mesh=MeshSpec(...))`` the [K, ...] corpus splits
        into contiguous per-device client blocks, each device paying
        ``ceil(K / n_devices)`` slots of the packed ceiling (the per-device
        pricing the plan auto rule uses), and the in-scan gather reads
        shard-locally before ``round_step``'s shard_map plane splits the
        cohort.
        """
        counts = validate_client_data(data)
        n_max = int(counts.max())
        arrays, shapes = {}, {}
        for name in data[0]:
            leaf0 = np.asarray(data[0][name])
            shapes[name] = leaf0.shape[1:]
            row = (math.prod(shapes[name]),) if shapes[name] else ()
            packed = np.zeros((len(data), n_max) + row, leaf0.dtype)
            for k, d in enumerate(data):
                packed[k, : counts[k]] = np.asarray(d[name]).reshape(
                    (counts[k],) + row)
            arrays[name] = cls._put(packed, shard_clients)
        return cls(arrays, cls._put(counts, shard_clients), shapes, seed)

    @classmethod
    def from_federated(cls, ds: FederatedDataset,
                       shard_clients: bool = True) -> "DeviceFederatedDataset":
        return cls.pack(ds.data, seed=ds.seed, shard_clients=shard_clients)

    @staticmethod
    def _put(x: np.ndarray, shard_clients: bool):
        if not shard_clients:
            return jnp.asarray(x)
        return sharding_rules.put_logical(
            x, *(("clients",) + (None,) * (x.ndim - 1)))

    # -- inspection -----------------------------------------------------
    @property
    def n_clients(self) -> int:
        return int(self.counts.shape[0])

    @property
    def n_max(self) -> int:
        return int(next(iter(self.arrays.values())).shape[1])

    @property
    def nbytes(self) -> int:
        """Packed device footprint (the K * n_max memory ceiling)."""
        return sum(int(a.nbytes) for a in self.arrays.values())

    def population(self) -> ClientPopulation:
        return ClientPopulation(counts=np.asarray(self.counts))

    def base_key(self):
        return jax.random.PRNGKey(self.seed)

    # -- the in-scan gather ---------------------------------------------
    def gather_round_batch(self, key: jax.Array, t, client_ids,
                           local_steps: int, batch_size: int):
        """Round ``t``'s ``[C, H, b, ...]`` batch stack, fully traceable.

        ``client_ids``: [C] int round participants (tracers fine — this is
        what `scan_rounds_ondevice` calls inside the scan body).  Draws are
        ``minibatch_indices`` with this dataset's keying, so the result is
        bit-equal to ``FederatedDataset.round_batches(client_ids, H, b, t)``
        on the same ``seed``; padding rows are never selected because every
        index is drawn from [0, n_k).

        Reads ``C * H * b`` rows per leaf: each leaf is indexed by (client,
        row) in one gather.  ``a[cid][idx]`` would, under vmap, first gather
        every participant's whole padded ``[n_max, ...]`` slab.
        """
        need = local_steps * batch_size

        def one(cid):
            idx = minibatch_indices(key, t, cid, self.counts[cid], need)
            return {
                name: a[cid, idx].reshape(
                    (local_steps, batch_size) + self.sample_shapes[name])
                for name, a in self.arrays.items()
            }

        return jax.vmap(one)(jnp.asarray(client_ids))
