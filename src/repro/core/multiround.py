"""Round-engine v2: R federated rounds as ONE compiled ``lax.scan``.

The per-round driver (`FederatedTrainer.run`) re-enters Python every round —
host sampling, `jnp.asarray` staging and a blocking metrics sync per round.
For the small rounds the paper benchmarks (LeNet / Shakespeare, milliseconds
of device work per round) that host overhead dominates wall-clock and hides
the FedMom speedup.  Here the whole round sequence is traced once:

    state, metrics = scan_rounds(loss_fn, opt, state, batches, weights, rcfg)

with ``batches`` pre-staged as [R, C, H, ...] (a *chunk* of rounds assembled
by the host prefetch queue in ``launch/train.py``), ``weights`` [R, C], and
optional per-round stepsizes [R] and heterogeneous-H_k step masks [R, C, H].
Every round reuses ``round_step`` verbatim, so all placement (`mesh`/`scan`)
and masking semantics — and the trajectory itself — are identical to the
per-round driver's (tests/test_multiround.py certifies allclose over 20+
rounds for FedAvg and FedMom).

Three tiers of host involvement, one algorithm:

* ``scan_rounds`` — batches pre-staged [R, C, H, ...] by the host (the
  prefetch queue in ``launch/train.py`` assembles them);
* ``scan_rounds_sampled`` — client *sampling* moves on-device
  (``Sampler.sample_device`` keyed by (key, t) inside the scan), batch data
  still host-assembled for the replayed client sets;
* ``scan_rounds_ondevice`` — the full data plane lives on device: the scan
  body samples S_t, gathers its [C, H, b, ...] minibatches from the dataset
  pytree (``(seed, t, client_id)``-keyed draws, bit-equal to the host
  assembly) and runs ``round_step`` — zero host round-trips per chunk.
  Diurnal/time-varying M rides along natively: the engine is lowered for
  the sampler's padded client extent and inactive slots carry zero weight.

The ``dataset`` of ``scan_rounds_ondevice`` is anything honoring the
``gather_round_batch(key, t, client_ids, H, b)`` contract: the fully packed
``DeviceFederatedDataset`` (data plane v1, ``plan="device"``) or a streaming
``data.stream.CacheView`` over a bounded shard cache (data plane v2,
``plan="streaming"`` — the fourth execution plane).  Both draw the same
keyed minibatch indices, so every path trains the same trajectory.

Mesh sharding: no scan body names a mesh axis.  Under an active data-
parallel mesh context (``ExecutionPlan(mesh=MeshSpec(...))`` activates it
around the plane dispatch), the in-scan ``round_step`` call itself enters
the explicit ``shard_map``+``psum`` plane — the body's gathered [C, H, ...]
cohort stack splits across devices at that boundary and the reduced delta
comes back replicated, so the carried ``ServerState`` is replicated on
every device and the scan structure here is unchanged.  ``mesh=None`` runs
this file's code on the pre-mesh single-device path bit for bit.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.round import (GATHER, RoundConfig, bucketed_round_step,
                              round_step)
from repro.core.server_opt import ServerOpt, ServerState


def scan_rounds(loss_fn: Callable, server_opt: ServerOpt, state: ServerState,
                batches: Any, weights: jax.Array, rcfg: RoundConfig,
                param_axes: Optional[Any] = None,
                lrs: Optional[jax.Array] = None,
                step_masks: Optional[jax.Array] = None) -> tuple:
    """Run ``R = weights.shape[0]`` rounds as a single ``lax.scan``.

    ``batches`` leaves: [R, C, H, ...]; ``weights``: [R, C];
    ``lrs``: optional [R] per-round gamma_t; ``step_masks``: optional
    [R, C, H].  Returns (final_state, metrics) with metrics leaves stacked
    over the round axis ([R] ``loss``/``delta_norm``/``round``).  The
    per-client ``losses`` stream is dropped from the carry-out to keep the
    transferred metrics O(R), not O(R*C).
    """
    if lrs is None:
        lrs = jnp.full((weights.shape[0],), rcfg.lr, jnp.float32)

    def body(st, xs):
        if step_masks is None:
            b, w, lr = xs
            m = None
        else:
            b, w, lr, m = xs
        st, metrics = round_step(loss_fn, server_opt, st, b, w, rcfg,
                                 param_axes=param_axes, lr=lr, step_mask=m)
        del metrics["losses"]
        return st, metrics

    xs = ((batches, weights, lrs) if step_masks is None
          else (batches, weights, lrs, step_masks))
    return jax.lax.scan(body, state, xs)


def scan_rounds_sampled(loss_fn: Callable, server_opt: ServerOpt,
                        state: ServerState, batches: Any, sampler,
                        key: jax.Array, t0: jax.Array, rcfg: RoundConfig,
                        param_axes: Optional[Any] = None,
                        lrs: Optional[jax.Array] = None,
                        step_masks: Optional[jax.Array] = None) -> tuple:
    """Like ``scan_rounds`` but draws S_t weights ON DEVICE inside the scan.

    ``sampler.sample_device(key, t)`` must be traceable (see
    ``core/sampling.py``); round ``t0 + r`` uses the weights it returns.
    ``batches`` must have been assembled (on host) for the *same* client
    indices the device draw produces — ``DeviceUniformSampler.sample`` is
    the replay that guarantees it.
    """
    R = jax.tree.leaves(batches)[0].shape[0]
    if lrs is None:
        lrs = jnp.full((R,), rcfg.lr, jnp.float32)
    rounds = t0 + jnp.arange(R, dtype=jnp.int32)

    def body(st, xs):
        if step_masks is None:
            b, t, lr = xs
            m = None
        else:
            b, t, lr, m = xs
        with jax.named_scope(GATHER):
            _, w = sampler.sample_device(key, t)
        st, metrics = round_step(loss_fn, server_opt, st, b, w, rcfg,
                                 param_axes=param_axes, lr=lr, step_mask=m)
        del metrics["losses"]
        return st, metrics

    xs = ((batches, rounds, lrs) if step_masks is None
          else (batches, rounds, lrs, step_masks))
    return jax.lax.scan(body, state, xs)


def scan_rounds_ondevice(loss_fn: Callable, server_opt: ServerOpt,
                         state: ServerState, dataset, sampler,
                         data_key: jax.Array, sample_key: jax.Array,
                         t0: jax.Array, n_rounds: int, rcfg: RoundConfig,
                         local_batch_size: int,
                         param_axes: Optional[Any] = None,
                         lrs: Optional[jax.Array] = None,
                         step_masks: Optional[jax.Array] = None) -> tuple:
    """Run ``n_rounds`` rounds with sampling AND data gather in the scan.

    ``dataset`` is a ``DeviceFederatedDataset`` or a streaming ``CacheView``
    (a pytree either way — pass it through jit as an argument, not a closure
    constant).  Round ``t = t0 + r``:
    ``sampler.sample_device(sample_key, t)`` draws S_t, the dataset gathers
    its ``[C, H, b, ...]`` minibatches keyed by ``(data_key, t, client_id)``
    and ``round_step`` consumes them — no host involvement between t0 and
    t0 + n_rounds.  The keyed draws replay exactly on host
    (``FederatedDataset.round_batches``), so this tier stays on the same
    trajectory as ``scan_rounds``/``scan_rounds_sampled`` fed by host
    assembly.  ``lrs``: optional [n_rounds]; ``step_masks``: optional
    [n_rounds, C, H] (host-stacked — O(R*C*H) scalars, not data).
    """
    if lrs is None:
        lrs = jnp.full((n_rounds,), rcfg.lr, jnp.float32)
    rounds = t0 + jnp.arange(n_rounds, dtype=jnp.int32)

    def body(st, xs):
        if step_masks is None:
            t, lr = xs
            m = None
        else:
            t, lr, m = xs
        with jax.named_scope(GATHER):
            idx, w = sampler.sample_device(sample_key, t)
            b = dataset.gather_round_batch(data_key, t, idx,
                                           rcfg.local_steps,
                                           local_batch_size)
        st, metrics = round_step(loss_fn, server_opt, st, b, w, rcfg,
                                 param_axes=param_axes, lr=lr, step_mask=m)
        del metrics["losses"]
        return st, metrics

    xs = ((rounds, lrs) if step_masks is None
          else (rounds, lrs, step_masks))
    return jax.lax.scan(body, state, xs)


def scan_rounds_bucketed(loss_fn: Callable, server_opt: ServerOpt,
                         state: ServerState, view, tiers_present: tuple,
                         tier_cids: tuple, tier_weights: tuple,
                         data_key: jax.Array, t0: jax.Array, n_rounds: int,
                         rcfg: RoundConfig, local_batch_size: int,
                         param_axes: Optional[Any] = None,
                         lrs: Optional[jax.Array] = None,
                         tier_masks: Optional[tuple] = None,
                         tier_idx: Optional[tuple] = None,
                         client_step_fn: Optional[Callable] = None) -> tuple:
    """Run ``n_rounds`` with HOST-staged, tier-bucketed cohorts.

    ``scan_rounds_ondevice`` samples S_t in the scan and gathers through a
    per-client ``lax.switch`` which — under vmap — reads ``need`` rows from
    EVERY tier corpus per participant, and then runs one C-wide launch per
    round.  The streaming plane already knows every chunk participant before
    dispatch (the ``KeyedReplayable`` lookahead that drives the H2D
    prefetch), so here the cohort is staged on host, grouped by cache tier,
    and each tier runs ONE sized launch: a switch-free
    ``CacheView.gather_tier_batch`` + per-tier vmapped local updates via
    ``bucketed_round_step``.

    ``tiers_present``: static tuple of the tier indices with any participant
    in the chunk.  ``tier_cids`` / ``tier_weights``: tuples (aligned with
    ``tiers_present``) of [R, C_i] arrays — per-round per-tier cohorts,
    right-padded with a chunk-resident client of the SAME tier at weight 0
    (the diurnal padded-C convention: zero weight => zero delta and excluded
    from the loss metric, so padding never perturbs the trajectory).
    ``tier_masks``: optional matching tuple of [R, C_i, H] H_k masks
    (padding rows carry all-ones masks so their eff_w stays exactly 0).

    ``tier_idx``: optional matching tuple of [R, C_i, H*b] HOST-staged
    minibatch indices (the eager replay of ``minibatch_indices`` — bit-equal
    to the in-scan draw).  When given (and no ``client_step_fn``), the chunk
    runs in fused-concat form: ONE switch-free row gather per tier covering
    all R rounds (``CacheView.gather_tier_rows`` over the flattened
    [R*C_i] cohort), one ``concatenate`` along the cohort axis, then the
    plain pre-staged ``scan_rounds`` engine — device-side chunk assembly.
    The in-scan PRNG chains, the per-participant tier switch and the
    per-tier launch pipelines all collapse: the compiled chunk carries
    FEWER device ops than the padded switch path (the dispatch-overhead
    win on CPU; the n_tiers-x gather-traffic win everywhere), at a
    transient [R, C, H, b, ...] device intermediate the ``chunk_rounds``
    knob bounds — gathered from the resident cache, never re-uploaded.
    Without it, every tier keeps its own keyed draw + sized launch via
    ``bucketed_round_step``.

    ``client_step_fn``: optional fused gather+local-SGD hook (see
    ``kernels/client_step``) replacing gather + vmap per tier:
    ``(view, tier, key, t, cids, w_c, lr, mask, local_steps, batch_size)
    -> (final_params [C_i, ...], losses [C_i])``.

    Same trajectory as the padded planes up to fp32 reduction order (the
    delta sums tier-by-tier instead of in cohort order): multi-tier chunks
    are tolerance-equal, single-tier chunks bit-equal.
    """
    R = int(n_rounds)
    if lrs is None:
        lrs = jnp.full((R,), rcfg.lr, jnp.float32)
    rounds = t0 + jnp.arange(R, dtype=jnp.int32)

    if tier_idx is not None and client_step_fn is None:
        # fused-concat form: the minibatch index draws were staged on the
        # host (bit-equal to the device draw — threefry is counter-based),
        # so the scan body is pure data motion + compute: one switch-free
        # sized gather per occupied tier, a cohort concat, and a single
        # C_tot-wide ``round_step``.  No in-scan PRNG, no lax.switch.  The
        # per-tier xs keep their own [R, C_i, ...] shapes so jit's shape
        # signature carries the full width split (a packed [R, C_tot]
        # layout would alias chunks whose totals collide).  Round metrics
        # stamp from the carried state.t, so no round index rides the scan.
        def body_concat(st, xs):
            if tier_masks is None:
                lr, cids, ws, idxs = xs
                ms = None
            else:
                lr, cids, ws, idxs, ms = xs
            with jax.named_scope(GATHER):
                parts = [
                    view.gather_tier_rows(tier, cids[i], idxs[i],
                                          rcfg.local_steps, local_batch_size)
                    for i, tier in enumerate(tiers_present)]
                batch = jax.tree.map(
                    lambda *ls: jnp.concatenate(ls, axis=0), *parts)
                w = jnp.concatenate(ws, axis=0)
                m = None if ms is None else jnp.concatenate(ms, axis=0)
            st, metrics = round_step(loss_fn, server_opt, st, batch,
                                     w, rcfg, param_axes=param_axes,
                                     lr=lr, step_mask=m)
            del metrics["losses"]
            return st, metrics

        xs = ((lrs, tier_cids, tier_weights, tier_idx)
              if tier_masks is None
              else (lrs, tier_cids, tier_weights, tier_idx, tier_masks))
        return jax.lax.scan(body_concat, state, xs)

    def body(st, xs):
        if tier_masks is None:
            t, lr, cids, ws = xs
            ms = None
        else:
            t, lr, cids, ws, ms = xs
        if client_step_fn is None:
            with jax.named_scope(GATHER):
                data = tuple(
                    view.gather_tier_batch(tier, data_key, t, cids[i],
                                           rcfg.local_steps,
                                           local_batch_size)
                    for i, tier in enumerate(tiers_present))
            update = None
        else:
            data = cids

            def update(w_c, i, cids_i, mask):
                return client_step_fn(view, tiers_present[i], data_key, t,
                                      cids_i, w_c, lr, mask,
                                      rcfg.local_steps, local_batch_size)
        st, metrics = bucketed_round_step(
            loss_fn, server_opt, st, data, ws, rcfg, param_axes=param_axes,
            lr=lr, tier_masks=ms, tier_update_fn=update)
        return st, metrics

    xs = ((rounds, lrs, tier_cids, tier_weights) if tier_masks is None
          else (rounds, lrs, tier_cids, tier_weights, tier_masks))
    return jax.lax.scan(body, state, xs)
