"""Fused client-step kernel: cache gather + H local SGD steps, one launch.

The bucketed streaming plane resolves a round's cohort to (tier, slot)
before dispatch, so the per-client work — fetch my shard's minibatch rows,
run H SGD steps — is a perfectly regular grid over the tier's clients.
Unfused, that is a gather kernel writing [C, H, b, ...] batches to HBM
followed by a vmapped local-update reading them straight back; fused, each
grid program pulls its client's ``[N, D]`` corpus slot into VMEM ONCE
(block selection via scalar-prefetched slot ids — the
``PrefetchScalarGridSpec`` pattern), reads its minibatch rows out of VMEM,
and carries the H-step parameter recurrence in registers.  The [C, H, b, D]
batch stack never exists in HBM: per client the traffic drops from
``n_tier * D + 2 * H * b * D`` (gather write + update read) to ``n_tier * D``.

Scope: the linear-regression family (MSE loss, plain-SGD local optimizer)
— the model the trajectory harness certifies — with the full
heterogeneous-H_k mask semantics of ``core.client.local_update``.  The
grids are sized by the TIER extent, so a 4-sample client's program loads a
4-row slot, never an n_max-row one.

TPU mapping: grid=(C,), one program per client.  Every VMEM block is 2-D
after squeezing its leading client/slot dim, with its last two dims equal
to the array's (the Mosaic tiling rule): the corpus slot ``[Np, Dp]``
(Dp = D padded to the 128 lane width), its targets ``[Np, 1]``, the
broadcast params ``[1, Dp]`` / ``[1, 1]``.  Row indices and step masks are
scalars, read from SMEM per client block; the slot ids are scalar-
prefetched because they pick the corpus block.  Each minibatch row is a
``[1, Dp]`` VPU vector and each residual a lane reduction, so the step is
exact fp32 elementwise work (no MXU precision pass).  H and b are static,
so the step/row loops unroll into straight-line code.  On a TPU the kernel
compiles; on the CPU it runs in interpret mode and the test sweeps pin it
to ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128                # VPU lane width: pad D up to a multiple
SUBLANE = 8               # fp32 sublane: pad N up to a multiple


def _client_body(slots_ref, x_ref, y_ref, idx_ref, m_ref, w_ref, b_ref,
                 lr_ref, wo_ref, bo_ref, lo_ref, *, local_steps: int,
                 batch_size: int):
    del slots_ref                      # consumed by the index maps
    w = w_ref[...]                     # [1, Dp] broadcast server model
    b = b_ref[...]                     # [1, 1]
    lr = lr_ref[...]                   # [1, 1]
    scale = 2.0 / batch_size
    lsum = jnp.zeros((1, 1), jnp.float32)
    asum = jnp.zeros((1, 1), jnp.float32)
    for h in range(local_steps):
        gw = jnp.zeros_like(w)
        gb = jnp.zeros((1, 1), jnp.float32)
        sq = jnp.zeros((1, 1), jnp.float32)
        for j in range(batch_size):
            r = idx_ref[0, h * batch_size + j]
            xr = x_ref[pl.ds(r, 1), :]                     # [1, Dp]
            err = (jnp.sum(xr * w, axis=1, keepdims=True) + b
                   - y_ref[pl.ds(r, 1), :])                # [1, 1]
            gw = gw + err * xr
            gb = gb + err
            sq = sq + err * err
        active = jnp.full((1, 1), m_ref[0, h], jnp.float32)
        on = active > 0
        w = jnp.where(on, w - lr * (scale * gw), w)
        b = jnp.where(on, b - lr * (scale * gb), b)
        lsum = lsum + (sq / batch_size) * active
        asum = asum + active
    wo_ref[...] = w
    bo_ref[...] = b
    lo_ref[...] = lsum / jnp.maximum(asum, 1.0)


@functools.partial(
    jax.jit, static_argnames=("local_steps", "batch_size", "interpret"))
def client_step_flat(xs: jax.Array, ys: jax.Array, slots: jax.Array,
                     idx: jax.Array, w: jax.Array, b: jax.Array,
                     lr: jax.Array, mask: jax.Array, *, local_steps: int,
                     batch_size: int, interpret: bool):
    """One launch over a tier's C clients (pre-padded operands).

    ``xs``: [S, Np, Dp] f32 tier corpus (Np mult of 8, Dp mult of 128);
    ``ys``: [S, Np, 1]; ``slots``: [C] int32 (scalar-prefetched — they
    select each program's corpus block); ``idx``: [C, H*b] int32 row
    indices; ``w``: [1, Dp]; ``b``/``lr``: [1, 1]; ``mask``: [C, H] f32.
    Returns ``(w_out [C, 1, Dp], b_out [C, 1, 1], loss [C, 1, 1])``.
    """
    C = slots.shape[0]
    _, Np, Dp = xs.shape
    H = local_steps
    sq = pl.Squeezed()
    smem = pltpu.MemorySpace.SMEM
    per_client = pl.BlockSpec((sq, 1, 1), lambda c, s: (c, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C,),
        in_specs=[
            pl.BlockSpec((sq, Np, Dp), lambda c, s: (s[c], 0, 0)),
            pl.BlockSpec((sq, Np, 1), lambda c, s: (s[c], 0, 0)),
            pl.BlockSpec((sq, 1, H * batch_size), lambda c, s: (c, 0, 0),
                         memory_space=smem),
            pl.BlockSpec((sq, 1, H), lambda c, s: (c, 0, 0),
                         memory_space=smem),
            pl.BlockSpec((1, Dp), lambda c, s: (0, 0)),
            pl.BlockSpec((1, 1), lambda c, s: (0, 0)),
            pl.BlockSpec((1, 1), lambda c, s: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((sq, 1, Dp), lambda c, s: (c, 0, 0)),
            per_client,
            per_client,
        ],
    )
    return pl.pallas_call(
        functools.partial(_client_body, local_steps=H,
                          batch_size=batch_size),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((C, 1, Dp), jnp.float32),
                   jax.ShapeDtypeStruct((C, 1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((C, 1, 1), jnp.float32)],
        interpret=interpret,
    )(slots, xs, ys, idx[:, None, :], mask[:, None, :], w, b, lr)
