"""Public wrapper for the fused client step — the gather+local-SGD contract.

The fusion contract (what the engine hands over, what it gets back):

* INPUT — streaming-cache coordinates, not batches.  The caller passes a
  tier's ``[S, N, ...]`` corpus plus per-client ``slots`` (cache slot ids)
  and ``idx`` (the ``minibatch_indices(key, t, cid, n_k, need)`` draws —
  the SAME keyed numbers every other plane uses, so fusion cannot move the
  trajectory).  No ``[C, H, b, ...]`` batch stack is ever materialized.
* COMPUTE — each client's program gathers its minibatch rows from its own
  slot in VMEM and runs H local SGD steps (Algorithm 2, plain-sgd local
  optimizer, MSE linear-regression loss), honoring ``step_mask`` exactly
  like ``core.client.local_update``: a masked step freezes the params and
  drops out of the loss mean.
* OUTPUT — ``(final_params, per-client mean loss)``: exactly what the
  engine's per-tier vmap would have produced, so
  ``core.round.bucketed_round_step`` aggregates either path identically
  (kernel math is fp32; vs the AD-derived reference it is tolerance-equal,
  not bit-equal — the gradients are hand-fused).

``linreg_tier_step`` adapts this to the ``client_step_fn`` hook of
``core.multiround.scan_rounds_bucketed`` for the linear-regression family
(fields ``{'x', 'y'}``, params ``{'w', 'b'}``).  Interpret mode resolves
through ``kernels._device.resolve_interpret``: compiled on a TPU,
interpreted on the CPU only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.data.federated import minibatch_indices
from repro.kernels._device import resolve_interpret
from repro.kernels.client_step import kernel as _k
from repro.kernels.client_step import ref as _ref


def _round_up(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def client_step(xs, ys, slots, idx, w, b, lr, local_steps: int,
                batch_size: int, step_mask=None, use_kernel: bool = True):
    """Fused gather + H local SGD steps over one tier's C clients.

    Array contract of ``ref.client_step`` (see there for shapes); this
    wrapper pads ``D`` to the 128-lane grid and ``N`` to the fp32 sublane
    (zero feature columns contribute zero gradient, and ``idx < n_k`` never
    reaches a padded row, so padding is exact), launches the kernel, and
    slices back.  Returns ``(w_out [C, D], b_out [C], mean_loss [C])``.
    """
    if not use_kernel:
        return _ref.client_step(xs, ys, slots, idx, w, b, lr, local_steps,
                                batch_size, step_mask)
    interpret = resolve_interpret((xs, ys, w))
    C = slots.shape[0]
    S, N, D = xs.shape
    H = int(local_steps)
    Np, Dp = _round_up(N, _k.SUBLANE), _round_up(D, _k.LANE)
    xs_p = jnp.pad(xs.astype(jnp.float32),
                   ((0, 0), (0, Np - N), (0, Dp - D)))
    ys_p = jnp.pad(ys.astype(jnp.float32), ((0, 0), (0, Np - N)))[..., None]
    w_p = jnp.pad(jnp.reshape(w, (1, D)).astype(jnp.float32),
                  ((0, 0), (0, Dp - D)))
    b_p = jnp.reshape(b, (1, 1)).astype(jnp.float32)
    lr_p = jnp.reshape(jnp.asarray(lr), (1, 1)).astype(jnp.float32)
    mask = (jnp.ones((C, H), jnp.float32) if step_mask is None
            else jnp.asarray(step_mask).astype(jnp.float32))
    wo, bo, lo = _k.client_step_flat(
        xs_p, ys_p, jnp.asarray(slots, jnp.int32),
        jnp.asarray(idx, jnp.int32), w_p, b_p, lr_p, mask,
        local_steps=H, batch_size=int(batch_size), interpret=interpret)
    return wo[:, 0, :D], bo[:, 0, 0], lo[:, 0, 0]


def linreg_tier_step(use_kernel: bool = True):
    """Build the ``client_step_fn`` hook ``scan_rounds_bucketed`` accepts.

    The hook draws the keyed minibatch indices (cheap scalar work), resolves
    clients to cache slots via the ``CacheView``, and hands the tier corpus
    straight to the fused kernel — requires the linear-regression family
    (dataset fields ``{'x', 'y'}``, params ``{'w': [D], 'b': []}``), fp32
    compute, and the plain-sgd local optimizer; the trainer validates those
    knobs before wiring the hook in.
    """
    def fn(view, tier, key, t, cids, w_c, lr, mask, local_steps,
           batch_size):
        arrs = view.tier_arrays[tier]
        if sorted(arrs) != ["x", "y"]:
            raise ValueError(
                "the fused client-step kernel covers the linear-regression "
                f"family (fields {{'x', 'y'}}); got {sorted(arrs)}")
        if not (isinstance(w_c, dict) and sorted(w_c) == ["b", "w"]):
            raise ValueError(
                "the fused client-step kernel needs linreg params "
                "{'w': [D], 'b': []}; got a different parameter tree")
        need = int(local_steps) * int(batch_size)
        cids = jnp.asarray(cids)
        slots = view.client_slots[cids]
        idx = jax.vmap(
            lambda c, n: minibatch_indices(key, t, c, n, need))(
                cids, view.counts[cids])
        wf, bf, losses = client_step(
            arrs["x"], arrs["y"], slots, idx, w_c["w"], w_c["b"], lr,
            local_steps, batch_size, step_mask=mask, use_kernel=use_kernel)
        return {"w": wf, "b": bf}, losses

    return fn
