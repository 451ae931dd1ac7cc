"""The federated round engine — Algorithm 1/3 steps 1-9 as one jitted
function.

A round:
  1. (host) the scheduler samples S_t, |S_t| = M clients and their weights
     n_k/n (repro.core.sampling);
  2. broadcast w_t to the M clients;
  3. every client runs H local optimizer steps (Algorithm 2);
  4. aggregate the *biased gradient* delta_t = sum_k (n_k/n)(w_t - w^k);
  5. the server optimizer (FedAvg / FedMom / ...) consumes delta_t.

Two placements with identical algorithm semantics (tests assert equality):

  * ``mesh``: clients tile the ('pod','data') mesh axes — step 3 is a vmap
    whose batch axis is sharded over those axes (spmd_axis_name), step 4 is
    a weighted reduction that XLA lowers to an all-reduce / reduce-scatter.
  * ``scan``: clients are sequential ``lax.scan`` iterations over FSDP-
    sharded parameters — for architectures whose replica cannot fit a single
    'model' slice (qwen2-vl-72b, grok-1-314b).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import client as client_lib
from repro.core import secure_agg
from repro.core.secure_agg import SecureAggSpec
from repro.core.server_opt import ServerOpt, ServerState
from repro.optim import local as local_opt_lib
from repro.sharding import (client_axis_size, current_mesh, shard_tree,
                            spmd_client_axes)

# Named scopes of a round's layers.  ``jax.named_scope`` is a compile-time
# label: it lands in every op's ``op_name`` metadata, which the profiler
# carries to the device trace, and adds no op.  Every plane runs its rounds
# through ``round_step`` / ``bucketed_round_step`` (the gathers through
# ``core/multiround.py``), so each layer has one name on every plane.
GATHER = "fed.gather"          # cohort draw and its minibatch gather
CLIENT = "fed.client"          # the clients' local updates
AGGREGATE = "fed.aggregate"    # the weighted delta, its norm, the loss
SERVER = "fed.server"          # the server optimizer's update


@dataclass(frozen=True)
class RoundConfig:
    clients_per_round: int          # M (= C, the lowered client extent)
    local_steps: int                # H
    lr: float                       # gamma_t (client stepsize)
    placement: str = "mesh"         # mesh | scan
    local_opt: str = "sgd"
    local_opt_kwargs: tuple = ()
    delta_dtype: str = "float32"    # bfloat16 variant = memory hillclimb
    compute_dtype: str = "bfloat16"
    # secure aggregation: when set, step 4's reduction runs through the
    # uint32-ring masking layer (core/secure_agg.py) — the server only ever
    # materializes the masked per-client messages and their (recovered)
    # sum.  Frozen + hashable, so it keys the jit caches like every other
    # RoundConfig field.  mesh placement only: the pairwise-mask grid is
    # [C, C, ...] per leaf, which the scan placement exists to avoid
    # (FSDP replicas too big for even a [C, ...] stack).
    secure: Optional[SecureAggSpec] = None


def _weighted_delta_stack(w_c, final, weights):
    """[C, ...] per-client weighted deltas ``(n_k/n)(w_t - w^k)`` in fp32
    — what a client would transmit (under masking) instead of the server
    reducing them itself."""
    C = weights.shape[0]
    return jax.tree.map(
        lambda w0, wk: weights.reshape((C,) + (1,) * w0.ndim)
        * (w0[None] - wk).astype(jnp.float32),
        w_c, final)


def _survivors(step_mask):
    """A client with zero unmasked local steps never reported its update
    (dropout) — its masked message is absent and its pairwise terms need
    recovery."""
    return None if step_mask is None else jnp.sum(step_mask, axis=1) > 0


def _secure_delta(spec, w_c, final, weights, step_mask, t, ddt):
    """Step 4 under secure aggregation: per-client weighted deltas in fp32
    (matching the open path's product precision), then the masked ring
    transport + dropout recovery, decoded and cast to the delta dtype."""
    y = _weighted_delta_stack(w_c, final, weights)
    return jax.tree.map(
        lambda d: d.astype(ddt),
        secure_agg.secure_weighted_sum(y, _survivors(step_mask), spec, t))


def _cast_tree(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def _client_mesh_axes() -> tuple:
    """The live mesh axes the cohort tiles, as a tuple (() outside a mesh)."""
    entry = spmd_client_axes()
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def _shard_map_round(loss_fn, opt, w_c, batches, weights, step_mask, lr,
                     mesh, axes, ddt):
    """Mesh-sharded step 3+4: the cohort splits into contiguous per-device
    blocks under ``shard_map`` over the client mesh axes; each shard vmaps
    its C/n clients and reduces its own weighted-delta partial in fp32, and
    a ``psum`` over those axes makes the delta replicated — the server
    update then runs identically on every device.  Per-shard loss streams
    stitch back to cohort order through the sharded out_spec (contiguous
    block splitting preserves the global client order).

    fp32 reduction-order caveat: the cohort einsum is reassociated
    (per-shard partial sums, then a cross-device psum tree), so the delta
    is tolerance-equal — not bit-equal — to the single-device plane.
    tests/test_mesh_shard.py certifies the trajectory within fp32 noise;
    the secure path never routes here (its uint32 ring reduction is exact
    and order-independent, so it stays on the GSPMD plane bit-equal).
    """
    lead = P(*axes)

    def body(w_rep, lr_rep, ws, bs, ms):
        def one(p, b, m=None):
            return client_lib.local_update(
                loss_fn, p, b, lr_rep, opt, step_mask=m)

        C_s = ws.shape[0]
        with jax.named_scope(CLIENT):
            # each shard's client replicas are device-varying: left
            # invariant, autodiff would transpose the implicit
            # invariant->varying cast into a psum and hand every client the
            # cross-device gradient sum
            local0 = jax.tree.map(
                lambda p: jax.lax.pcast(
                    jnp.broadcast_to(p[None], (C_s,) + p.shape), axes,
                    to="varying"), w_rep)
            final, losses = (jax.vmap(one)(local0, bs) if ms is None
                             else jax.vmap(one)(local0, bs, ms))
        with jax.named_scope(AGGREGATE):
            part = jax.tree.map(
                lambda w0, wk: jnp.einsum(
                    "c,c...->...", ws, w0[None] - wk,
                    preferred_element_type=jnp.float32),
                w_rep, final)
            return jax.lax.psum(part, axes), losses

    rep = jax.tree.map(lambda _: P(), w_c)
    if step_mask is None:
        fn = jax.shard_map(
            lambda w, l, ws, bs: body(w, l, ws, bs, None), mesh=mesh,
            in_specs=(rep, P(), lead, lead), out_specs=(rep, lead))
        delta, losses = fn(w_c, lr, weights, batches)
    else:
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(rep, P(), lead, lead, lead), out_specs=(rep, lead))
        delta, losses = fn(w_c, lr, weights, batches, step_mask)
    with jax.named_scope(AGGREGATE):
        return jax.tree.map(lambda d: d.astype(ddt), delta), losses


def round_step(loss_fn, server_opt: ServerOpt, state: ServerState,
               batches: Any, weights: jax.Array, rcfg: RoundConfig,
               param_axes: Optional[Any] = None,
               lr: Optional[jax.Array] = None,
               step_mask: Optional[jax.Array] = None) -> tuple:
    """One federated round.

    ``batches``: pytree with leading axes [C, H, ...] (C clients x H local
    minibatches).  ``weights``: [C] fp32, the n_k/n of the sampled clients.
    ``lr``: dynamic client stepsize gamma_t (overrides rcfg.lr) — the
    decreasing schedules of Corollary 3.3 pass it per round.
    ``step_mask``: optional [C, H] {0,1} — heterogeneous local work H_k per
    client (stragglers report after H_k < H steps).  Aggregation keeps the
    raw n_k/n weights: eq. (3) is exact under partial work because a
    fully-masked client returns w^k = w_t and contributes zero to delta_t —
    identical to eq. (2) leaving its weight mass on w_t.  Only the *metrics*
    reweight (renormalized over clients that did any work), so the reported
    loss is not diluted by inactive slots.
    Returns (new_state, metrics).
    """
    C = weights.shape[0]
    if rcfg.secure is not None and rcfg.placement != "mesh":
        raise ValueError(
            "secure aggregation needs placement='mesh' (got "
            f"{rcfg.placement!r}): the pairwise-mask grid is [C, C, ...] "
            "per leaf, and scan placement exists for FSDP replicas that "
            "cannot even hold the [C, ...] cohort stack")
    opt = local_opt_lib.get(rcfg.local_opt, **dict(rcfg.local_opt_kwargs))
    lr = jnp.asarray(rcfg.lr if lr is None else lr, jnp.float32)
    with jax.named_scope(CLIENT):
        w_c = _cast_tree(state.w, jnp.dtype(rcfg.compute_dtype))
    ddt = jnp.dtype(rcfg.delta_dtype)

    def one_client(p, b, m=None):
        return client_lib.local_update(loss_fn, p, b, lr, opt, step_mask=m)

    if rcfg.placement == "mesh":
        mesh = current_mesh()
        axes = _client_mesh_axes()
        # explicit shard_map plane: only when the live mesh is a pure
        # data-parallel mesh over exactly the client axes (a 'model' axis
        # would need param sharding inside the shard, which is the GSPMD
        # path's job), the cohort divides evenly into contiguous per-device
        # blocks, and aggregation is open (secure's [C, C, ...] pairwise
        # mask grid must see the whole cohort; its uint32 ring reduction is
        # also exact under GSPMD, so it loses nothing by staying there)
        if (rcfg.secure is None and mesh is not None and axes
                and set(mesh.axis_names) == set(axes)
                and C % client_axis_size() == 0):
            delta, losses = _shard_map_round(
                loss_fn, opt, w_c, batches, weights, step_mask, lr,
                mesh, axes, ddt)
        else:
            with jax.named_scope(CLIENT):
                local0 = jax.tree.map(
                    lambda p: jnp.broadcast_to(p[None], (C,) + p.shape), w_c)
                if param_axes is not None:
                    local0 = shard_tree(local0, param_axes,
                                        prefix=("clients",))
                spmd = spmd_client_axes()
                vmapped = jax.vmap(one_client, spmd_axis_name=spmd) if spmd \
                    else jax.vmap(one_client)
                if step_mask is None:
                    final, losses = vmapped(local0, batches)
                else:
                    final, losses = vmapped(local0, batches, step_mask)
                if param_axes is not None:
                    final = shard_tree(final, param_axes,
                                       prefix=("clients",))
            # products and accumulation stay fp32 no matter delta_dtype:
            # rounding the n_k/n weights (or the per-client diffs) to bf16
            # BEFORE the reduction leaks weight mass under skewed n_k; only
            # the final result is rounded to ddt, so the bf16 delta is the
            # correctly-rounded fp32 reduction
            with jax.named_scope(AGGREGATE):
                if rcfg.secure is not None:
                    delta = _secure_delta(rcfg.secure, w_c, final, weights,
                                          step_mask, state.t, ddt)
                else:
                    delta = jax.tree.map(
                        lambda w0, wk: jnp.einsum(
                            "c,c...->...", weights, w0[None] - wk,
                            preferred_element_type=jnp.float32).astype(ddt),
                        w_c, final)
    elif rcfg.placement == "scan":
        if param_axes is not None:
            # scan placement promises FSDP-sharded params: constrain the
            # broadcast model once here, and the accumulator every iteration
            # below, so XLA keeps the sharded layout through the whole scan
            # instead of gathering the replica per client
            w_c = shard_tree(w_c, param_axes)

        def body(acc, xs):
            if step_mask is None:
                b_k, a_k = xs
                m_k = None
            else:
                b_k, a_k, m_k = xs
            with jax.named_scope(CLIENT):
                wk, loss = one_client(w_c, b_k, m_k)
            # fp32 accumulator to match the mesh-path einsum; cast to ddt
            # once after the scan
            with jax.named_scope(AGGREGATE):
                acc = jax.tree.map(
                    lambda d, w0, wkl: d + a_k
                    * (w0 - wkl).astype(jnp.float32),
                    acc, w_c, wk)
                if param_axes is not None:
                    acc = shard_tree(acc, param_axes)
            return acc, loss
        with jax.named_scope(AGGREGATE):
            delta0 = jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), w_c)
        xs = ((batches, weights) if step_mask is None
              else (batches, weights, step_mask))
        delta, losses = jax.lax.scan(body, delta0, xs)
        with jax.named_scope(AGGREGATE):
            delta = jax.tree.map(lambda d: d.astype(ddt), delta)
    else:
        raise ValueError(rcfg.placement)

    with jax.named_scope(SERVER):
        new_state = server_opt.update(state, delta)
    with jax.named_scope(AGGREGATE):
        eff_w = weights
        if step_mask is not None:
            eff_w = weights * (jnp.sum(step_mask, axis=1) > 0)
        wsum = jnp.maximum(jnp.sum(eff_w), 1e-12)
        metrics = {
            "loss": jnp.sum(eff_w * losses) / wsum,
            "losses": losses,
            "delta_norm": _global_norm(delta),
            # clients that contributed work to eq. (3): positive weight AND
            # >=1 unmasked local step — dropouts/stragglers that finished
            # nothing and zero-weight padded slots both fall out, so a
            # scenario run's per-round completion is observable from the
            # metrics stream
            "completed": jnp.sum(eff_w > 0).astype(jnp.int32),
            "round": state.t,
        }
    return new_state, metrics


def bucketed_round_step(loss_fn, server_opt: ServerOpt, state: ServerState,
                        tier_data: tuple, tier_weights: tuple,
                        rcfg: RoundConfig,
                        param_axes: Optional[Any] = None,
                        lr: Optional[jax.Array] = None,
                        tier_masks: Optional[tuple] = None,
                        tier_update_fn=None) -> tuple:
    """One federated round dispatched as per-tier SIZED launches.

    The padded ``round_step`` lowers every round for the full client extent
    C with n_max-shaped gathers; here the cohort arrives pre-grouped by the
    cache's n_k size tiers (``data/stream.py tier_layout``) and each tier
    runs one launch of its own extent — a 4-sample crowdsensing client never
    rides in the same vmap as a 4096-sample one.

    ``tier_data`` / ``tier_weights`` / ``tier_masks``: tuples over OCCUPIED
    tiers; ``tier_weights[i]``: [C_i] fp32 n_k/n (zero-weight right-padding
    follows the diurnal padded-C convention — zero delta, excluded from the
    loss metric); ``tier_data[i]``: the tier's [C_i, H, b, ...] batch stack,
    or an opaque payload when ``tier_update_fn`` is given;
    ``tier_masks[i]``: optional [C_i, H] heterogeneous-H_k masks.

    ``tier_update_fn(w_c, i, data, mask) -> (final_params [C_i, ...],
    losses [C_i])`` replaces the gathered-batch vmap (the fused
    ``kernels/client_step`` hook plugs in here).

    Reduction-order caveat: the delta is accumulated tier-by-tier (each tier
    one fp32 einsum) instead of a single cohort-order einsum, so multi-tier
    results are tolerance-equal to the padded path (fp32 reassociation),
    while a single occupied tier is bit-equal.  Under ``rcfg.secure`` the
    caveat DISAPPEARS: each tier is masked as its own sub-cohort (round key
    folded with the tier index) and the per-tier ring totals accumulate
    with exact, order-independent uint32 ring addition, decoded once — so
    multi-tier secure dispatch is bit-equal to the padded secure path.
    Returns (new_state, metrics) with the same keys as ``round_step`` minus
    the per-client ``losses`` stream (its width varies per tier).
    """
    if rcfg.placement != "mesh":
        raise ValueError(
            "bucketed dispatch is a per-tier vmap — placement='mesh' only "
            f"(got {rcfg.placement!r}); use the padded round_step for scan")
    opt = local_opt_lib.get(rcfg.local_opt, **dict(rcfg.local_opt_kwargs))
    lr = jnp.asarray(rcfg.lr if lr is None else lr, jnp.float32)
    with jax.named_scope(CLIENT):
        w_c = _cast_tree(state.w, jnp.dtype(rcfg.compute_dtype))
    ddt = jnp.dtype(rcfg.delta_dtype)

    def one_client(p, b, m=None):
        return client_lib.local_update(loss_fn, p, b, lr, opt, step_mask=m)

    def run_tier(w_c, i, batches, mask):
        C_i = tier_weights[i].shape[0]
        local0 = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (C_i,) + p.shape), w_c)
        if param_axes is not None:
            local0 = shard_tree(local0, param_axes, prefix=("clients",))
        spmd = spmd_client_axes()
        vmapped = jax.vmap(one_client, spmd_axis_name=spmd) if spmd \
            else jax.vmap(one_client)
        final, losses = (vmapped(local0, batches) if mask is None
                         else vmapped(local0, batches, mask))
        if param_axes is not None:
            final = shard_tree(final, param_axes, prefix=("clients",))
        return final, losses

    update = tier_update_fn or run_tier
    secure = rcfg.secure
    with jax.named_scope(AGGREGATE):
        if secure is not None:
            acc = jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.uint32), w_c)
            round_key = (secure_agg.round_mask_key(secure, state.t)
                         if secure.masked else None)
        else:
            acc = jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), w_c)
        loss_num = jnp.zeros((), jnp.float32)
        loss_den = jnp.zeros((), jnp.float32)
        completed = jnp.zeros((), jnp.int32)
    for i, (data, weights) in enumerate(zip(tier_data, tier_weights)):
        mask = None if tier_masks is None else tier_masks[i]
        with jax.named_scope(CLIENT):
            final, losses = update(w_c, i, data, mask)
        with jax.named_scope(AGGREGATE):
            if secure is not None:
                tier_key = (jax.random.fold_in(round_key, i)
                            if secure.masked else None)
                y = _weighted_delta_stack(w_c, final, weights)
                ring = secure_agg.masked_ring_sum(
                    y, _survivors(mask), secure, tier_key)
                acc = jax.tree.map(lambda a, r: a + r, acc, ring)
            else:
                acc = jax.tree.map(
                    lambda d, w0, wk: d + jnp.einsum(
                        "c,c...->...", weights, w0[None] - wk,
                        preferred_element_type=jnp.float32),
                    acc, w_c, final)
            eff_w = weights
            if mask is not None:
                eff_w = weights * (jnp.sum(mask, axis=1) > 0)
            loss_num = loss_num + jnp.sum(eff_w * losses)
            loss_den = loss_den + jnp.sum(eff_w)
            completed = completed + jnp.sum(eff_w > 0).astype(jnp.int32)
    with jax.named_scope(AGGREGATE):
        if secure is not None:
            delta = jax.tree.map(
                lambda d: d.astype(ddt), secure_agg.decode(acc, secure))
        else:
            delta = jax.tree.map(lambda d: d.astype(ddt), acc)
    with jax.named_scope(SERVER):
        new_state = server_opt.update(state, delta)
    with jax.named_scope(AGGREGATE):
        metrics = {
            "loss": loss_num / jnp.maximum(loss_den, 1e-12),
            "delta_norm": _global_norm(delta),
            "completed": completed,
            "round": state.t,
        }
    return new_state, metrics


def _global_norm(tree):
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree.leaves(tree)))


# ---------------------------------------------------------------------------
# eq. (2) reference implementation — used by tests to certify that the
# biased-gradient form (eq. 3, used above) is *identical* to model averaging
# ---------------------------------------------------------------------------
def model_averaging_reference(w_t, local_models, weights):
    """eq. (2): w_{t+1} = sum_{k in S_t} (n_k/n) w^k + (1 - sum n_k/n) w_t."""
    active_mass = jnp.sum(weights)
    return jax.tree.map(
        lambda w0, wk: jnp.einsum(
            "c,c...->...", weights, wk.astype(jnp.float32))
        + (1.0 - active_mass) * w0.astype(jnp.float32),
        w_t, local_models)
