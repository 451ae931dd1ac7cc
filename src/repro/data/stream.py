"""Streaming shard-cached federated data plane (Data plane v2, tiered slots).

The device-resident plane (``data/device.py``) pays ``K * n_max * itemsize``
per field — the whole padded corpus up front.  In the paper's motivating
setting (mobile crowdsensing, devices "continuously generate a significant
quantity of data") and at real federated scale (LEAF FEMNIST/Shakespeare with
thousands of clients, heavily skewed n_k) that ceiling does not fit device
memory.  This plane keeps the corpus on HOST as per-client shards and holds
only the shards of *upcoming participants* in a bounded device-side cache.

Slot-size tiers: federated corpora are heavily unbalanced (McMahan et al.
2016; Li et al. 2019), so padding EVERY cache slot to the global ``n_max``
lets one huge client inflate the footprint of all resident clients.  The
cache therefore buckets clients into power-of-two size tiers
(``n_tier = min(next_pow2(n_k), n_max)``) and allocates per-tier
``[slots_t, n_tier, ...]`` device arrays with per-tier LRU: a 3-sample
crowdsensing client costs a 4-row slot, not an ``n_max``-row one.  At
Zipfian n_k skew this cuts cache device bytes several-fold at equal
hit-rate.  ``tiers=1`` recovers the uniform single-tier layout (every slot
``n_max`` rows); ``tiers=m`` caps the number of distinct tiers by merging
the smallest buckets upward.

* ``StreamingFederatedDataset`` — host per-client shards (same field dtypes
  and the same ``(seed, t, client_id)``-keyed minibatch draws as the other
  planes), plus the packing metadata the cache needs (``tier_layout``:
  tier sizes, per-client tier assignment, tiered byte accounting).  Built
  either from a materialized ``data`` list or from a lazy ``ShardProvider``
  (declared counts/fields; shards synthesized or loaded on first cache
  miss, keyed by client id) — the provider path removes the host-RAM cap
  on K entirely: millions of Zipf clients cost [K] ints of metadata;
* ``ShardCache`` — per-tier ``[slots_t, n_tier, ...]`` device arrays per
  field with per-tier LRU eviction over client shards.  ``capacity_clients``
  guarantees any request of that many distinct clients fits regardless of
  how they spread over tiers (each tier gets ``min(K_t, capacity)`` slots);
  ``capacity_bytes`` is translated to the largest such guarantee whose
  tiered footprint fits the budget — a budget below one slot per occupied
  tier raises (never silently exceeded).  ``ensure(client_ids)`` uploads the
  missing shards (one batched scatter per tier per field, padded only to the
  tier's rows) and refreshes LRU recency in LAST-use order of the raw
  ``client_ids`` sequence; ``view()`` snapshots the cache as a ``CacheView``;
* ``CacheView`` — a pytree with the exact ``gather_round_batch`` contract of
  ``DeviceFederatedDataset``, so ``core.multiround.scan_rounds_ondevice``
  consumes it unchanged: the in-scan gather resolves a participant through a
  client→(tier, slot) indirection — row-indexing (``a[slot][idx]`` with
  ``idx < n_k <= n_tier``) yields the same ``[need, ...]`` shape in every
  tier, so the per-client tier dispatch is a traceable ``lax.switch`` — and
  draws ``minibatch_indices`` keyed by the TRUE client id and n_k, bit-equal
  to host assembly and to the device-resident gather, keeping all driver
  paths on one trajectory.

Overlapped H2D prefetch: ``DeviceUniformSampler``'s host path replays the
device draw (the ``KeyedReplayable`` capability), so chunk i+1's
participants are known before its compute is dispatched.  The streaming
plane (``FederatedTrainer.run(n, plan="streaming")``) calls ``ensure`` for
chunk i+1 right after dispatching chunk i: the per-tier scatters are
dispatched asynchronously and the uploads overlap chunk i's scanned compute.
Updates are functional (``.at[slots].set``), so the arrays captured by chunk
i's ``CacheView`` are immutable — later uploads and evictions can never
corrupt an in-flight chunk (double buffering for free).
"""
from __future__ import annotations

import glob as _glob
import json
import os
from bisect import bisect_left
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sampling import ClientPopulation
from repro.data.federated import (CorpusSchemaError, FederatedDataset,
                                  check_shard, minibatch_indices,
                                  shard_schema, validate_client_data)
from repro.sharding import rules as sharding_rules


@runtime_checkable
class ShardProvider(Protocol):
    """Capability: a corpus whose client shards are synthesized or loaded
    ON DEMAND, never all materialized in host RAM.

    ``StreamingFederatedDataset`` caps K at host memory when built from a
    materialized ``data`` list; a provider instead *declares* the corpus
    shape up front (``counts``: [K] n_k, ``fields``: {name: (tail_shape,
    dtype)}) and produces one client's shard only when the ``ShardCache``
    first misses on it.  ``shard(client_id)`` must be a pure function of
    ``client_id`` (key any synthesis RNG by ``(provider seed, client_id)``)
    so a re-fetch after eviction — or after a resume — returns the SAME
    rows, which is what keeps provider-backed trajectories bit-reproducible.
    Each fetched shard is validated against the declared schema
    (``CorpusSchemaError`` naming the client on any mismatch).
    """

    @property
    def n_clients(self) -> int: ...

    @property
    def counts(self) -> np.ndarray: ...        # [K] n_k, int

    @property
    def fields(self) -> Dict[str, tuple]: ...  # {name: (tail_shape, dtype)}

    def shard(self, client_id: int) -> Dict[str, np.ndarray]: ...


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << (int(n) - 1).bit_length()


@dataclass(frozen=True)
class TierLayout:
    """How a corpus buckets into slot-size tiers (host metadata only).

    ``sizes``: ascending tier row capacities; the last always covers n_max.
    ``tier_of``: [K] tier index per client (the smallest tier whose rows
    hold the client's n_k).  ``tier_counts``: clients per tier.
    ``row_nbytes``: device bytes of ONE sample row summed over fields — a
    tier-``t`` slot costs ``sizes[t] * row_nbytes``.
    """
    sizes: Tuple[int, ...]
    tier_of: np.ndarray
    tier_counts: Tuple[int, ...]
    row_nbytes: int

    @property
    def n_tiers(self) -> int:
        return len(self.sizes)

    def slot_nbytes(self, tier: int) -> int:
        return self.sizes[tier] * self.row_nbytes

    def bytes_for_capacity(self, capacity: int) -> int:
        """Tiered device footprint of a cache guaranteeing ``capacity``
        distinct clients per request: each tier holds
        ``min(K_t, capacity)`` slots of its own row size."""
        return sum(min(k_t, capacity) * self.slot_nbytes(t)
                   for t, k_t in enumerate(self.tier_counts))

    @property
    def min_viable_bytes(self) -> int:
        """One slot in every occupied tier — the smallest honest cache."""
        return self.bytes_for_capacity(1)

    def capacity_for_bytes(self, budget: int) -> Optional[int]:
        """Largest per-request client guarantee whose tiered footprint fits
        ``budget`` (bytes), or None when even one slot per occupied tier
        does not fit.  bytes_for_capacity is monotone in capacity, so a
        linear scan up to max(K_t) suffices (K is host metadata, tiny)."""
        if self.bytes_for_capacity(1) > budget:
            return None
        cap = 1
        for c in range(2, max(self.tier_counts) + 1):
            if self.bytes_for_capacity(c) > budget:
                break
            cap = c
        return cap


class StreamingFederatedDataset:
    """Host shards (materialized OR provider-backed) + packing metadata.

    Two construction paths, one declared schema:

    * ``data``: list over clients of dicts of arrays (first axis = samples),
      exactly the ``FederatedDataset`` layout; per-field dtypes preserved.
      Every client is validated against client 0's schema up front
      (``CorpusSchemaError`` naming the divergent client — this used to
      silently trust client 0 and crash later at upload time).
    * ``provider``: a lazy ``ShardProvider`` — ``counts``/``fields`` come
      from the provider's DECLARATION, and a client's rows are synthesized
      or loaded only on the first ``ShardCache`` miss (validated against
      the declaration on every fetch).  This is what lets Zipf corpora with
      millions of clients run under the streaming plane: host RAM holds
      [K] metadata, never K shards.

    ``seed`` keys the minibatch draws like every other plane.

    ``validate`` (provider path only) controls schema validation of
    fetched shards: ``"first"`` (default) validates each client ONCE — an
    eviction-refetch of an already-passed client skips the re-check, which
    at million-client scale is pure overhead on rows the provider is
    contractually obliged to reproduce bit-identically; ``"always"``
    re-validates every fetch (distrust the provider's purity);
    ``"never"`` skips validation entirely.  Failures raise
    ``CorpusSchemaError`` naming the client either way.
    """

    VALIDATE_MODES = ("always", "first", "never")

    def __init__(self, data: Optional[List[Dict[str, np.ndarray]]] = None,
                 seed: int = 0, provider: Optional[ShardProvider] = None,
                 validate: str = "first"):
        if validate not in self.VALIDATE_MODES:
            raise ValueError(
                f"validate must be one of {self.VALIDATE_MODES}, "
                f"got {validate!r}")
        if (data is None) == (provider is None):
            raise ValueError(
                "StreamingFederatedDataset takes exactly one of data= (a "
                "materialized per-client shard list) or provider= (a lazy "
                "ShardProvider)")
        if provider is not None:
            if not isinstance(provider, ShardProvider):
                raise TypeError(
                    f"provider must implement the ShardProvider protocol "
                    f"(n_clients, counts, fields, shard(client_id)); "
                    f"{type(provider).__name__} does not")
            counts = np.asarray(provider.counts, np.int64)
            if counts.ndim != 1 or len(counts) != provider.n_clients \
                    or len(counts) == 0:
                raise CorpusSchemaError(
                    f"provider declares n_clients={provider.n_clients} but "
                    f"counts has shape {counts.shape}: want a non-empty "
                    f"[K] vector")
            if (counts < 1).any():
                bad = int(np.argmin(counts))
                raise CorpusSchemaError(
                    f"provider declares n_k = {int(counts[bad])} for client "
                    f"{bad}: every client needs n_k >= 1 (the keyed "
                    f"minibatch draw is undefined on an empty span)",
                    client=bad)
            fields = {name: (tuple(tail), np.dtype(dt))
                      for name, (tail, dt) in sorted(provider.fields.items())}
            if not fields:
                raise CorpusSchemaError("provider declares no fields")
        else:
            counts = validate_client_data(data)
            fields = {name: schema for name, schema
                      in sorted(shard_schema(data[0]).items())}
        self.data = data
        self.provider = provider
        self.counts = np.asarray(counts, np.int32)
        self.seed = seed
        self.n_max = int(self.counts.max())
        self.fields = fields
        self.validate = validate
        self._validated: set = set()   # clients passed under "first"

    @classmethod
    def from_federated(cls, ds: FederatedDataset) -> "StreamingFederatedDataset":
        return cls(ds.data, seed=ds.seed)

    @classmethod
    def from_provider(cls, provider: ShardProvider, seed: int = 0,
                      validate: str = "first") -> "StreamingFederatedDataset":
        """Lazy corpus over a ``ShardProvider`` declaration (see class
        docstring); ``seed`` keys the minibatch draws, ``validate`` the
        per-fetch schema check policy."""
        return cls(provider=provider, seed=seed, validate=validate)

    # -- inspection -----------------------------------------------------
    @property
    def n_clients(self) -> int:
        return len(self.counts)

    @property
    def row_nbytes(self) -> int:
        """Device bytes of one sample row, summed over fields."""
        return sum(int(np.prod(tail, dtype=np.int64))
                   * np.dtype(dtype).itemsize
                   for tail, dtype in self.fields.values())

    @property
    def slot_nbytes(self) -> int:
        """Device bytes one UNIFORM cache slot costs (padded to n_max) —
        what every resident client pays in the tiers=1 layout."""
        return self.n_max * self.row_nbytes

    @property
    def packed_nbytes(self) -> int:
        """What the device-RESIDENT plane would pay (the K * n_max ceiling);
        compare against a cache budget to pick a plane."""
        return self.n_clients * self.slot_nbytes

    def tier_layout(self, tiers: Optional[int] = None) -> TierLayout:
        """Bucket clients into power-of-two slot-size tiers.

        Natural tiers are the distinct ``min(next_pow2(n_k), n_max)`` values
        present in the corpus (a client whose n_k is an exact power of two
        lands in that tier, not the next one).  ``tiers=m`` keeps only the
        m LARGEST natural sizes — clients of merged-away small tiers pad up
        into the smallest kept tier — so ``tiers=1`` is exactly the uniform
        n_max-slot layout.  ``tiers=None`` keeps every natural tier.
        """
        natural = sorted({min(next_pow2(int(n)), self.n_max)
                          for n in self.counts})
        if tiers is not None:
            if int(tiers) < 1:
                raise ValueError(f"tiers must be >= 1, got {tiers!r}")
            natural = natural[-int(tiers):]
        sizes = tuple(natural)
        tier_of = np.asarray(
            [bisect_left(sizes, min(next_pow2(int(n)), self.n_max))
             for n in self.counts], np.int32)
        tier_counts = tuple(int((tier_of == t).sum())
                            for t in range(len(sizes)))
        return TierLayout(sizes=sizes, tier_of=tier_of,
                          tier_counts=tier_counts,
                          row_nbytes=self.row_nbytes)

    def population(self) -> ClientPopulation:
        return ClientPopulation(counts=np.asarray(self.counts))

    def base_key(self):
        return jax.random.PRNGKey(self.seed)

    def shard(self, cid: int) -> Dict[str, np.ndarray]:
        """Client ``cid``'s raw (unpadded) shard.

        Materialized path: a host-list lookup.  Provider path: ONE
        ``provider.shard(cid)`` call — potentially expensive synthesis or
        I/O — validated against the declared schema AND the declared
        ``counts[cid]`` before any device upload sees it (a provider that
        drifts from its declaration raises ``CorpusSchemaError`` naming the
        client, not a downstream scatter-shape crash).  The ``validate``
        knob scopes the check: every fetch (``"always"``), first fetch per
        client (``"first"``, the default — eviction-refetch of a
        passed client skips it), or not at all (``"never"``)."""
        if self.provider is None:
            return self.data[cid]
        cid = int(cid)
        shard = self.provider.shard(cid)
        if self.validate == "always" or (self.validate == "first"
                                         and cid not in self._validated):
            check_shard(shard, self.fields, cid, n_k=int(self.counts[cid]),
                        source="provider shard for")
            if self.validate == "first":
                self._validated.add(cid)
        return shard

    def padded_client(self, cid: int,
                      rows: Optional[int] = None) -> Dict[str, np.ndarray]:
        """All of client ``cid``'s fields padded to [rows, ...] (host) from
        ONE ``shard()`` fetch; ``rows`` defaults to the global n_max, a
        tier passes its own size.  The cache fill path uses this so a
        provider synthesizes each missing client exactly once per miss,
        not once per field."""
        shard = self.shard(cid)
        n_rows = self.n_max if rows is None else rows
        out = {}
        for name, (tail, dtype) in self.fields.items():
            arr = np.asarray(shard[name])
            padded = np.zeros((n_rows,) + tail, dtype)
            padded[: len(arr)] = arr
            out[name] = padded
        return out

    def padded_shard(self, cid: int, name: str,
                     rows: Optional[int] = None) -> np.ndarray:
        """Client ``cid``'s field ``name`` padded to [rows, ...] (host);
        ``rows`` defaults to the global n_max, a tier passes its own size.
        Prefer ``padded_client`` when touching several fields of one
        client — this re-fetches the shard per call."""
        tail, dtype = self.fields[name]
        out = np.zeros((self.n_max if rows is None else rows,) + tail, dtype)
        arr = np.asarray(self.shard(cid)[name])
        out[: len(arr)] = arr
        return out


@jax.tree_util.register_pytree_node_class
class CacheView:
    """Immutable snapshot of a ``ShardCache`` for one chunk dispatch.

    Same ``gather_round_batch`` contract as ``DeviceFederatedDataset`` (so
    ``scan_rounds_ondevice`` takes it verbatim), over per-tier compacted
    ``[slots_t, n_tier, ...]`` corpora: ``client_tiers``/``client_slots``
    ([K] int32, slot -1 when absent) resolve a participant to its tier and
    cache slot, while the draw stays keyed by the true client id and true
    n_k — bit-equal to every other plane.
    """

    def __init__(self, tier_arrays: Tuple[Dict[str, jax.Array], ...],
                 counts: jax.Array, client_tiers: jax.Array,
                 client_slots: jax.Array, seed: int = 0):
        self.tier_arrays = tuple(tier_arrays)
        self.counts = counts            # [K] true n_k (not slot-compacted)
        self.client_tiers = client_tiers  # [K] int32 client -> tier
        self.client_slots = client_slots  # [K] int32 client -> slot in tier
        self.seed = seed

    # -- pytree protocol (jit-arg friendly) -----------------------------
    def tree_flatten(self):
        keys = tuple(sorted(self.tier_arrays[0]))
        children = tuple(arrs[k] for arrs in self.tier_arrays
                         for k in keys) + (
            self.counts, self.client_tiers, self.client_slots)
        return children, (keys, len(self.tier_arrays), self.seed)

    @classmethod
    def tree_unflatten(cls, aux, children):
        keys, n_tiers, seed = aux
        *leaves, counts, client_tiers, client_slots = children
        per = len(keys)
        tier_arrays = tuple(
            dict(zip(keys, leaves[t * per:(t + 1) * per]))
            for t in range(n_tiers))
        return cls(tier_arrays, counts, client_tiers, client_slots, seed)

    def base_key(self):
        return jax.random.PRNGKey(self.seed)

    # -- the in-scan gather (fused with sampling by scan_rounds_ondevice)
    def gather_round_batch(self, key: jax.Array, t, client_ids,
                           local_steps: int, batch_size: int):
        """Round ``t``'s ``[C, H, b, ...]`` batch stack, fully traceable.

        Indirection happens only on the DATA fetch: every tier's
        ``[slots_t, n_tier, ...]`` corpus is row-indexed at the client's
        slot and a select keeps the client's own tier — every tier yields
        the same ``[need, ...]`` shape, and ``idx < n_k <= n_tier`` in the
        client's own tier (other tiers' reads are clamped and dropped).
        The index draw is ``minibatch_indices(key, t, cid, n_k, need)``
        with the true client id — the same numbers every other plane draws.

        This is what ``vmap`` makes of a per-client ``lax.switch`` anyway,
        written out: a batched switch also takes the corpora it closes
        over as batched operands, broadcast to ``[C, slots_t, ...]``.  On
        one device XLA folds that broadcast into the gather; under a mesh
        the partitioner splits it first and keeps it (14 GB of temporaries
        for a 500-client LeNet corpus compiled for a v5e, over the chip's
        16 GB).

        Cost note: the gather reads ``need`` rows from EACH tier corpus per
        participant (n_tiers x the uniform gather traffic for an
        O(H*b)-row fetch — small next to the local-step compute on those
        same rows, and bounded by ``CacheSpec.tiers`` when a corpus spans
        many natural power-of-two buckets).
        """
        need = local_steps * batch_size

        def rows_in(tier, slot, idx):
            return {name: a[slot][idx]
                    for name, a in self.tier_arrays[tier].items()}

        def one(cid):
            slot = self.client_slots[cid]
            idx = minibatch_indices(key, t, cid, self.counts[cid], need)
            rows = rows_in(0, slot, idx)
            for t_ in range(1, len(self.tier_arrays)):
                other = rows_in(t_, slot, idx)
                rows = {name: jnp.where(self.client_tiers[cid] == t_,
                                        other[name], r)
                        for name, r in rows.items()}
            return {
                name: r.reshape((local_steps, batch_size) + r.shape[1:])
                for name, r in rows.items()
            }

        return jax.vmap(one)(jnp.asarray(client_ids))

    def gather_tier_batch(self, tier: int, key: jax.Array, t, client_ids,
                          local_steps: int, batch_size: int):
        """Switch-free gather for clients KNOWN to live in ``tier``.

        The bucketed dispatch (``core.multiround.scan_rounds_bucketed``)
        stages each round's cohort per tier on host, so the per-client
        ``lax.switch`` of ``gather_round_batch`` — which under vmap reads
        every tier corpus per participant — collapses to one direct
        row-index into the single ``[slots_t, n_tier, ...]`` corpus.  The
        index draw is the identical ``minibatch_indices(key, t, cid, n_k,
        need)``, so the rows are bit-equal to every other plane's gather.

        The caller guarantees residency and tier membership: a client of a
        different tier would row-index the wrong corpus (garbage rows, not
        an error) — zero-weight padding therefore always reuses a client of
        the SAME tier.
        """
        need = local_steps * batch_size
        arrs = self.tier_arrays[tier]

        def one(cid):
            slot = self.client_slots[cid]
            idx = minibatch_indices(key, t, cid, self.counts[cid], need)
            return {
                name: a[slot][idx].reshape(
                    (local_steps, batch_size) + a.shape[2:])
                for name, a in arrs.items()
            }

        return jax.vmap(one)(jnp.asarray(client_ids))

    def gather_tier_rows(self, tier: int, client_ids, idx,
                         local_steps: int, batch_size: int):
        """``gather_tier_batch`` with the index draw already staged.

        ``idx``: [C_i, need] precomputed minibatch indices (the host replay
        of ``minibatch_indices`` — threefry is counter-based, so the staged
        draw is bit-equal to the in-scan one).  Staging moves the per-tier
        fold-in/randint op chains out of the compiled chunk entirely: the
        bucketed scan body keeps only the two-level row gather per tier,
        which is what lets its device op count undercut the padded
        switch-gather path.  Same residency/tier-membership caveats as
        ``gather_tier_batch``; padding rows may carry any in-range indices
        (their zero weight drops them from delta and loss alike).
        """
        arrs = self.tier_arrays[tier]

        def one(cid, ix):
            slot = self.client_slots[cid]
            return {
                name: a[slot][ix].reshape(
                    (local_steps, batch_size) + a.shape[2:])
                for name, a in arrs.items()
            }

        return jax.vmap(one)(jnp.asarray(client_ids), idx)


class ShardCache:
    """Bounded device-side LRU cache of client shards, tiered by n_k.

    ``capacity_clients`` is a per-request guarantee: any ``ensure`` of that
    many distinct clients fits no matter how they spread over size tiers
    (tier t gets ``min(K_t, capacity)`` slots of its own row size, so total
    allocated slots can exceed the capacity while total bytes stay far below
    the uniform layout under skew).  ``capacity_bytes`` is translated to the
    largest such guarantee whose tiered footprint fits (tighter wins when
    both are given); a budget below one slot per occupied tier raises a
    ``ValueError`` naming the minimum viable budget instead of silently
    exceeding the declaration.  ``ensure`` raises when one request needs
    more distinct clients than the capacity guarantee — the caller must
    shrink ``chunk_rounds`` or grow the cache, never silently thrash.

    ``tiers``: None keeps every natural power-of-two tier; ``tiers=1`` is
    the uniform single-tier layout (every slot n_max rows); ``tiers=m``
    merges the smallest buckets upward into at most m tiers.

    Slot updates are functional scatters, so views snapshotted before an
    ``ensure`` stay valid while it uploads (this is what lets the streaming
    driver prefetch chunk i+1 during chunk i's compute).
    """

    def __init__(self, dataset: StreamingFederatedDataset,
                 capacity_clients: Optional[int] = None,
                 capacity_bytes: Optional[int] = None,
                 tiers: Optional[int] = None):
        if capacity_clients is None and capacity_bytes is None:
            raise ValueError(
                "ShardCache needs capacity_clients or capacity_bytes")
        layout = dataset.tier_layout(tiers)
        cap = dataset.n_clients
        if capacity_clients is not None:
            cap = min(cap, max(1, int(capacity_clients)))
        if capacity_bytes is not None:
            by_bytes = layout.capacity_for_bytes(int(capacity_bytes))
            if by_bytes is None:
                raise ValueError(
                    f"capacity_bytes={int(capacity_bytes)} is below the "
                    f"minimum viable cache budget: one slot in each of the "
                    f"{layout.n_tiers} occupied size tier(s) (rows "
                    f"{layout.sizes}) needs {layout.min_viable_bytes} B — "
                    f"raise capacity_bytes to at least that, or declare "
                    f"capacity_clients instead")
            cap = min(cap, by_bytes)
        self.capacity = cap
        self.layout = layout
        self.tier_slots = tuple(min(k_t, cap) for k_t in layout.tier_counts)
        self.dataset = dataset
        self.tier_arrays = [
            {name: self._put(np.zeros((slots_t, size_t) + tail, dtype))
             for name, (tail, dtype) in dataset.fields.items()}
            for slots_t, size_t in zip(self.tier_slots, layout.sizes)
        ]
        self._counts_dev = jnp.asarray(dataset.counts)
        self._tier_of = layout.tier_of
        self._slot_of: List[Dict[int, int]] = [
            {} for _ in range(layout.n_tiers)]
        self._lru: List["OrderedDict[int, None]"] = [
            OrderedDict() for _ in range(layout.n_tiers)]
        self.hits = self.misses = self.evictions = 0
        # per-tier churn attribution (sums equal the cache-wide counters):
        # the chunk metrics records surface these as cache_tier_* deltas
        self.tier_hits = [0] * layout.n_tiers
        self.tier_misses = [0] * layout.n_tiers
        self.tier_evictions = [0] * layout.n_tiers

    @staticmethod
    def _put(x: np.ndarray):
        # slot order is LRU-arbitrary, so the cached corpus is placed by the
        # 'cache_slots' rule (replicated: a round's slots would otherwise
        # scatter across data shards)
        return sharding_rules.put_logical(
            x, *(("cache_slots",) + (None,) * (x.ndim - 1)))

    # -- inspection -----------------------------------------------------
    @property
    def slots(self) -> int:
        """Total allocated slots across tiers (>= capacity when clients
        spread over tiers; bytes, not slot count, is the footprint)."""
        return sum(self.tier_slots)

    @property
    def tier_sizes(self) -> Tuple[int, ...]:
        return self.layout.sizes

    @property
    def nbytes(self) -> int:
        """Device footprint of the cache (<= dataset.packed_nbytes)."""
        return sum(int(a.nbytes) for arrs in self.tier_arrays
                   for a in arrs.values())

    @property
    def hit_rate(self) -> float:
        return self.hits / max(self.hits + self.misses, 1)

    def resident(self) -> set:
        return set().union(*(set(s) for s in self._slot_of))

    # -- population -----------------------------------------------------
    def ensure(self, client_ids) -> None:
        """Make every client in ``client_ids`` resident (per-tier LRU
        eviction, one batched async scatter per tier per field for the
        missing shards).  ``client_ids`` may repeat — pass the chunk's RAW
        per-round participant sequence so recency refresh lands in
        LAST-use order (eviction must never target a client the chunk's
        final round just used)."""
        seq = [int(c) for c in client_ids]
        need = list(OrderedDict((c, None) for c in seq))
        distinct = set(need)
        if len(distinct) > self.capacity:
            raise ValueError(
                f"chunk needs {len(distinct)} distinct clients but the "
                f"shard cache guarantees {self.capacity} slots; lower "
                f"chunk_rounds or raise the cache capacity")
        fresh_by_tier: Dict[int, List[int]] = {}
        n_fresh = 0
        for cid in need:
            tier = int(self._tier_of[cid])
            if cid not in self._slot_of[tier]:
                fresh_by_tier.setdefault(tier, []).append(cid)
                self.tier_misses[tier] += 1
                n_fresh += 1
            else:
                self.tier_hits[tier] += 1
        self.hits += len(need) - n_fresh
        self.misses += n_fresh
        for tier, fresh in fresh_by_tier.items():
            slot_of, lru = self._slot_of[tier], self._lru[tier]
            assigned = []
            for cid in fresh:
                if len(slot_of) < self.tier_slots[tier]:
                    slot = len(slot_of)
                else:
                    # guaranteed to exist: distinct-in-tier <= min(K_t,
                    # capacity) = tier_slots[tier] once the global check
                    # above passed
                    victim = next(c for c in lru if c not in distinct)
                    slot = slot_of.pop(victim)
                    del lru[victim]
                    self.evictions += 1
                    self.tier_evictions[tier] += 1
                slot_of[cid] = slot
                assigned.append(slot)
            idx = jnp.asarray(np.asarray(assigned, np.int32))
            rows = self.layout.sizes[tier]
            arrs = self.tier_arrays[tier]
            # one shard fetch per fresh client (a lazy provider synthesizes
            # each missing client exactly once, not once per field)
            shards = [self.dataset.padded_client(cid, rows=rows)
                      for cid in fresh]
            for name in arrs:
                stacked = np.stack([s[name] for s in shards])
                arrs[name] = arrs[name].at[idx].set(self._put(stacked))
        for cid in seq:             # refresh recency in LAST-use order
            lru = self._lru[int(self._tier_of[cid])]
            lru[cid] = None
            lru.move_to_end(cid)

    def view(self) -> CacheView:
        """Snapshot the cache for one chunk dispatch (see class docstring)."""
        client_slots = np.full(self.dataset.n_clients, -1, np.int32)
        for slot_of in self._slot_of:
            for cid, slot in slot_of.items():
                client_slots[cid] = slot
        return CacheView(tuple(dict(arrs) for arrs in self.tier_arrays),
                         self._counts_dev, jnp.asarray(self._tier_of),
                         jnp.asarray(client_slots), self.dataset.seed)


class MeshShardedCache:
    """Per-shard ``ShardCache`` composition for the mesh-sharded planes.

    Clients are assigned to data shards by ``cid % n_shards`` (static, so
    the assignment never depends on LRU history), and each shard owns a
    FULL-capacity ``ShardCache`` over its own client subset — per-device
    capacity semantics: the declared ``capacity_clients``/``capacity_bytes``
    budget is what ONE device's cache may hold, matching the per-device
    memory pricing of the mesh auto rule.  Splitting one budget n ways
    instead would let an unlucky shard assignment evict mid-chunk.

    ``ensure`` routes each shard its own sub-sequence (order preserved, so
    per-shard LRU recency still lands in last-use order); ``view`` composes
    ONE ``CacheView`` by concatenating the per-shard tier corpora along the
    slot axis and offsetting each shard's client->slot table by the slots
    of the shards before it — so ``gather_round_batch`` (and the bucketed
    ``gather_tier_*``) consume the composed view verbatim and the
    trajectory is bit-equal to the single-cache plane (the gather contract
    keys draws by true client id and n_k, never by slot).  Device
    placement of the composed corpus follows the replicated 'cache_slots'
    rule (slot order is LRU-arbitrary — see FED_MESH_RULES); the per-shard
    structure is the client->shard bookkeeping that keeps every device's
    working set bounded by its own declared budget.

    Counter properties aggregate across shards, so the trainer's
    ``cache_*`` chunk metrics and the perf lanes read it like a plain
    ``ShardCache``.
    """

    def __init__(self, dataset: StreamingFederatedDataset, n_shards: int,
                 capacity_clients: Optional[int] = None,
                 capacity_bytes: Optional[int] = None,
                 tiers: Optional[int] = None):
        if int(n_shards) < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards!r}")
        self.dataset = dataset
        self.n_shards = int(n_shards)
        self.shards = tuple(
            ShardCache(dataset, capacity_clients=capacity_clients,
                       capacity_bytes=capacity_bytes, tiers=tiers)
            for _ in range(self.n_shards))
        self.layout = self.shards[0].layout
        self._counts_dev = self.shards[0]._counts_dev
        self._tier_of = self.layout.tier_of

    def shard_of(self, cid: int) -> int:
        return int(cid) % self.n_shards

    # -- aggregate inspection (ShardCache-compatible) -------------------
    @property
    def capacity(self) -> int:
        """Total distinct-client guarantee across shards — exact only for
        a shard-balanced request; the per-shard guarantee is what
        ``ensure`` actually enforces."""
        return sum(s.capacity for s in self.shards)

    @property
    def slots(self) -> int:
        return sum(s.slots for s in self.shards)

    @property
    def tier_slots(self) -> Tuple[int, ...]:
        return tuple(sum(s.tier_slots[t] for s in self.shards)
                     for t in range(self.layout.n_tiers))

    @property
    def tier_sizes(self) -> Tuple[int, ...]:
        return self.layout.sizes

    @property
    def nbytes(self) -> int:
        return sum(s.nbytes for s in self.shards)

    @property
    def hits(self) -> int:
        return sum(s.hits for s in self.shards)

    @property
    def misses(self) -> int:
        return sum(s.misses for s in self.shards)

    @property
    def evictions(self) -> int:
        return sum(s.evictions for s in self.shards)

    @property
    def tier_hits(self) -> List[int]:
        return [sum(s.tier_hits[t] for s in self.shards)
                for t in range(self.layout.n_tiers)]

    @property
    def tier_misses(self) -> List[int]:
        return [sum(s.tier_misses[t] for s in self.shards)
                for t in range(self.layout.n_tiers)]

    @property
    def tier_evictions(self) -> List[int]:
        return [sum(s.tier_evictions[t] for s in self.shards)
                for t in range(self.layout.n_tiers)]

    @property
    def hit_rate(self) -> float:
        return self.hits / max(self.hits + self.misses, 1)

    def resident(self) -> set:
        return set().union(*(s.resident() for s in self.shards))

    # -- population -----------------------------------------------------
    def ensure(self, client_ids) -> None:
        """Route each client to its shard's cache (sub-sequences keep the
        chunk's raw order, so per-shard LRU recency refresh stays in
        last-use order)."""
        per_shard: List[list] = [[] for _ in range(self.n_shards)]
        for cid in client_ids:
            per_shard[int(cid) % self.n_shards].append(int(cid))
        for shard, seq in zip(self.shards, per_shard):
            if seq:
                shard.ensure(seq)

    def view(self) -> CacheView:
        """One composed ``CacheView`` over all shards: per-tier corpora
        concatenate along the slot axis in shard order, and each shard's
        client->slot entries shift by the cumulative slot count of earlier
        shards.  The concat is a device op per chunk dispatch — O(cache
        bytes), overlapped with compute like the uploads themselves."""
        tier_arrays = []
        for t in range(self.layout.n_tiers):
            names = self.shards[0].tier_arrays[t].keys()
            tier_arrays.append({
                name: jnp.concatenate(
                    [s.tier_arrays[t][name] for s in self.shards], axis=0)
                for name in names})
        client_slots = np.full(self.dataset.n_clients, -1, np.int32)
        offsets = [0] * self.layout.n_tiers
        for s in self.shards:
            for t, slot_of in enumerate(s._slot_of):
                for cid, slot in slot_of.items():
                    client_slots[cid] = slot + offsets[t]
            for t in range(self.layout.n_tiers):
                offsets[t] += s.tier_slots[t]
        return CacheView(tuple(tier_arrays), self._counts_dev,
                         jnp.asarray(self._tier_of),
                         jnp.asarray(client_slots), self.dataset.seed)


# ---------------------------------------------------------------------------
# on-disk corpora: DiskShardProvider + writer + LEAF ingestion
# ---------------------------------------------------------------------------
CORPUS_FORMAT = "repro-fleet-corpus"
CORPUS_VERSION = 1
CORPUS_LAYOUTS = ("npy-packed", "npz-per-client")


def _dtype_tag(dt) -> str:
    return np.dtype(dt).name


def _field_dtype(arr: np.ndarray) -> np.dtype:
    """LEAF json carries untyped numbers: floats land as float64, ints as
    int64 — narrow to the repo's float32/int32 corpus convention."""
    if np.issubdtype(arr.dtype, np.floating):
        return np.dtype(np.float32)
    if np.issubdtype(arr.dtype, np.integer):
        return np.dtype(np.int32)
    raise CorpusSchemaError(
        f"unsupported field dtype {arr.dtype} (want numeric)")


def parse_leaf_dir(leaf_dir: str):
    """Parse a LEAF-format directory (``*.json`` files with ``users`` /
    ``num_samples`` / ``user_data``, the layout the LEAF benchmark suite
    emits) into ``(counts, fields, shards, users)`` — host arrays, floats
    narrowed to float32 and ints to int32.  Files are visited in sorted
    name order and users in file order, so the client-id assignment is
    deterministic across runs and machines."""
    files = sorted(_glob.glob(os.path.join(leaf_dir, "*.json")))
    if not files:
        raise CorpusSchemaError(
            f"no LEAF json files in {leaf_dir!r} (want the LEAF layout: "
            f"*.json with users/num_samples/user_data)")
    users, shards = [], []
    for path in files:
        with open(path) as f:
            blob = json.load(f)
        for key in ("users", "user_data"):
            if key not in blob:
                raise CorpusSchemaError(
                    f"{path!r} is not LEAF-format: missing {key!r}")
        declared = dict(zip(blob["users"],
                            blob.get("num_samples", [])))
        for user in blob["users"]:
            ud = blob["user_data"][user]
            shard = {}
            for name, rows in sorted(ud.items()):
                arr = np.asarray(rows)
                shard[name] = arr.astype(_field_dtype(arr))
            n = len(next(iter(shard.values())))
            if user in declared and int(declared[user]) != n:
                raise CorpusSchemaError(
                    f"LEAF user {user!r} declares num_samples="
                    f"{declared[user]} but carries {n} rows",
                    client=len(users))
            users.append(user)
            shards.append(shard)
    fields = {name: schema
              for name, schema in sorted(shard_schema(shards[0]).items())}
    counts = np.array([check_shard(s, fields, k, source="LEAF user")
                       for k, s in enumerate(shards)], np.int64)
    return counts, fields, shards, users


def write_disk_corpus(root: str, provider: ShardProvider,
                      layout: str = "npy-packed") -> str:
    """Materialize any ``ShardProvider`` as an on-disk corpus directory
    readable by ``DiskShardProvider``; returns ``root``.

    ``npy-packed``: one row-concatenated ``<field>.npy`` per field (written
    via ``open_memmap``, so host RAM never holds the packed corpus) —
    the mmap-backed layout for big corpora.  ``npz-per-client``: one
    ``shards/<cid>.npz`` per client — the simple layout for small ones.
    Either way ``counts.npy`` + ``manifest.json`` declare the schema.
    """
    if layout not in CORPUS_LAYOUTS:
        raise ValueError(
            f"layout must be one of {CORPUS_LAYOUTS}, got {layout!r}")
    os.makedirs(root, exist_ok=True)
    counts = np.asarray(provider.counts, np.int64)
    fields = {name: (tuple(tail), np.dtype(dt))
              for name, (tail, dt) in sorted(provider.fields.items())}
    np.save(os.path.join(root, "counts.npy"), counts)
    if layout == "npy-packed":
        total = int(counts.sum())
        offsets = np.concatenate([[0], np.cumsum(counts)])
        mms = {name: np.lib.format.open_memmap(
                   os.path.join(root, f"{name}.npy"), mode="w+",
                   dtype=dtype, shape=(total,) + tail)
               for name, (tail, dtype) in fields.items()}
        for cid in range(len(counts)):
            shard = provider.shard(cid)
            lo, hi = int(offsets[cid]), int(offsets[cid + 1])
            for name, mm in mms.items():
                mm[lo:hi] = np.asarray(shard[name], mm.dtype)
        for mm in mms.values():
            mm.flush()
    else:
        sdir = os.path.join(root, "shards")
        os.makedirs(sdir, exist_ok=True)
        for cid in range(len(counts)):
            shard = provider.shard(cid)
            np.savez(os.path.join(sdir, f"{cid}.npz"),
                     **{name: np.asarray(shard[name], dtype)
                        for name, (_, dtype) in fields.items()})
    manifest = {
        "format": CORPUS_FORMAT,
        "version": CORPUS_VERSION,
        "layout": layout,
        "n_clients": int(len(counts)),
        "counts": "counts.npy",
        "fields": {name: {"shape": list(tail), "dtype": _dtype_tag(dtype)}
                   for name, (tail, dtype) in fields.items()},
    }
    with open(os.path.join(root, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
        f.write("\n")
    return root


def leaf_to_corpus(leaf_dir: str, out_dir: str,
                   layout: str = "npz-per-client") -> str:
    """Convert a LEAF-format directory into a ``DiskShardProvider`` corpus
    (see ``write_disk_corpus`` for the layouts); returns ``out_dir``."""
    parsed_counts, parsed_fields, parsed_shards, _ = parse_leaf_dir(leaf_dir)

    class _Parsed:
        n_clients = len(parsed_counts)
        counts = parsed_counts
        fields = parsed_fields

        def shard(self, cid):
            return parsed_shards[int(cid)]

    return write_disk_corpus(out_dir, _Parsed(), layout=layout)


class DiskShardProvider:
    """``ShardProvider`` over an on-disk corpus directory.

    Accepts either a manifest-declared corpus (``manifest.json`` +
    ``counts.npy`` + field files, as ``write_disk_corpus`` /
    ``leaf_to_corpus`` emit) or a raw LEAF-format directory of json files
    (parsed once at construction — json cannot be mmapped; convert big
    LEAF corpora with ``leaf_to_corpus`` to get the mmap-backed layout).

    ``npy-packed`` corpora are opened with ``np.load(mmap_mode="r")``:
    construction touches only the [K] count vector and the file headers,
    and ``shard(cid)`` copies the client's row span out of the mapping —
    host RAM never holds the corpus.  ``shard`` is a pure function of
    ``client_id`` over immutable files, so an eviction-refetch (or a
    resumed run) returns bit-identical rows — the property that keeps
    disk-backed trajectories bit-reproducible.
    """

    def __init__(self, root: str):
        self.root = str(root)
        manifest_path = os.path.join(self.root, "manifest.json")
        if os.path.exists(manifest_path):
            self._init_manifest(manifest_path)
        elif _glob.glob(os.path.join(self.root, "*.json")):
            self._init_leaf()
        else:
            raise CorpusSchemaError(
                f"{self.root!r} is neither a manifest-declared corpus "
                f"(manifest.json) nor a LEAF-format directory (*.json)")

    def _init_manifest(self, manifest_path: str):
        with open(manifest_path) as f:
            manifest = json.load(f)
        if manifest.get("format") != CORPUS_FORMAT:
            raise CorpusSchemaError(
                f"{manifest_path!r} is not a {CORPUS_FORMAT} manifest "
                f"(format={manifest.get('format')!r})")
        if manifest.get("version") != CORPUS_VERSION:
            raise CorpusSchemaError(
                f"corpus version {manifest.get('version')!r} unsupported "
                f"(this build reads version {CORPUS_VERSION})")
        layout = manifest.get("layout")
        if layout not in CORPUS_LAYOUTS:
            raise CorpusSchemaError(
                f"corpus layout {layout!r} unsupported (want one of "
                f"{CORPUS_LAYOUTS})")
        self.layout = layout
        counts = np.load(os.path.join(self.root, manifest["counts"]))
        counts = np.asarray(counts, np.int64)
        if counts.ndim != 1 or len(counts) != int(manifest["n_clients"]):
            raise CorpusSchemaError(
                f"counts file has shape {counts.shape} but the manifest "
                f"declares n_clients={manifest['n_clients']}")
        self._counts = counts
        self._fields = {
            name: (tuple(spec["shape"]), np.dtype(spec["dtype"]))
            for name, spec in sorted(manifest["fields"].items())}
        if not self._fields:
            raise CorpusSchemaError("corpus manifest declares no fields")
        self._shards_mem = None
        if layout == "npy-packed":
            self._offsets = np.concatenate([[0], np.cumsum(counts)])
            total = int(self._offsets[-1])
            self._mm = {}
            for name, (tail, dtype) in self._fields.items():
                mm = np.load(os.path.join(self.root, f"{name}.npy"),
                             mmap_mode="r")
                if mm.shape != (total,) + tail or mm.dtype != dtype:
                    raise CorpusSchemaError(
                        f"packed field {name!r} is {mm.shape}/{mm.dtype} "
                        f"but the manifest declares "
                        f"{(total,) + tail}/{dtype}")
                self._mm[name] = mm
        else:
            sdir = os.path.join(self.root, "shards")
            for probe in (0, len(counts) - 1):
                p = os.path.join(sdir, f"{probe}.npz")
                if not os.path.exists(p):
                    raise CorpusSchemaError(
                        f"npz-per-client corpus missing shard file {p!r}",
                        client=probe)
            self._sdir = sdir

    def _init_leaf(self):
        self.layout = "leaf-json"
        counts, fields, shards, users = parse_leaf_dir(self.root)
        self._counts = counts
        self._fields = fields
        self._shards_mem = shards
        self.users = users

    @classmethod
    def from_leaf(cls, leaf_dir: str) -> "DiskShardProvider":
        """Open a raw LEAF-format directory directly (parse-once path;
        equivalent to ``DiskShardProvider(leaf_dir)``)."""
        return cls(leaf_dir)

    # -- ShardProvider protocol ------------------------------------------
    @property
    def n_clients(self) -> int:
        return len(self._counts)

    @property
    def counts(self) -> np.ndarray:
        return self._counts

    @property
    def fields(self) -> Dict[str, tuple]:
        return self._fields

    def shard(self, client_id: int) -> Dict[str, np.ndarray]:
        cid = int(client_id)
        if not 0 <= cid < self.n_clients:
            raise IndexError(
                f"client {cid} outside corpus [0, {self.n_clients})")
        if self._shards_mem is not None:          # leaf-json
            return self._shards_mem[cid]
        if self.layout == "npy-packed":
            lo, hi = int(self._offsets[cid]), int(self._offsets[cid + 1])
            return {name: np.array(mm[lo:hi])
                    for name, mm in self._mm.items()}
        with np.load(os.path.join(self._sdir, f"{cid}.npz")) as z:
            return {name: z[name] for name in self._fields}
