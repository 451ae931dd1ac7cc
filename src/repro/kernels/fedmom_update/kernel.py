"""Fused server-update kernels (the paper's eq. (9) as one HBM pass).

Unfused, the FedMom update
    v' = w - eta * delta
    w' = v' + beta * (v' - v)
is three elementwise HLO ops: 6 HBM reads + 4 writes of the full parameter
vector.  Fused, it is 3 reads (w, v, delta) + 2 writes (w', v') — a 2x cut
on the server-update memory term, which is what dominates the server step
for multi-billion-parameter states (see EXPERIMENTS.md §Perf).  The same
tiling carries the heavy-ball (FedAvgM) update
    m' = beta * m + delta
    w' = w - eta * m'
so both momentum server optimizers route through one fused pass.

TPU mapping: a 1-D parameter stream is viewed as [rows, LANE] with
LANE=128 (VPU lane width) and tiled [BLOCK_ROWS, 128] into VMEM.  Pure
elementwise VPU work — no MXU — so the only roofline term is HBM bandwidth,
which the fusion halves.

Tree packing: ``fused_update_tree`` by default *concatenates* all leaves of
the parameter pytree into one flat stream and launches a single kernel —
one launch and one tile-pad for the whole model instead of one per leaf
(ragged leaves, bf16 leaves and scalars all ride the same stream; elementwise
updates don't care about leaf boundaries).  ``fuse_tree=False`` keeps the
per-leaf launches for comparison/debugging.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
BLOCK_ROWS = 256          # [256, 128] fp32 tile = 128 KiB per operand


def _fedmom_body(w_ref, v_ref, d_ref, wo_ref, vo_ref, *, eta: float,
                 beta: float):
    w = w_ref[...]
    v = v_ref[...]
    d = d_ref[...]
    v_new = w - eta * d
    wo_ref[...] = v_new + beta * (v_new - v)
    vo_ref[...] = v_new


def _fedavgm_body(w_ref, m_ref, d_ref, wo_ref, mo_ref, *, eta: float,
                  beta: float):
    m_new = beta * m_ref[...] + d_ref[...]
    wo_ref[...] = w_ref[...] - eta * m_new
    mo_ref[...] = m_new


_BODIES = {"fedmom": _fedmom_body, "fedavgm": _fedavgm_body}


@functools.partial(jax.jit,
                   static_argnames=("kind", "eta", "beta", "interpret"))
def fused_flat(w: jax.Array, s: jax.Array, delta: jax.Array, kind: str,
               eta: float, beta: float, *, interpret: bool):
    """w / momentum-state s / delta: [rows, 128] fp32 (rows a multiple of
    BLOCK_ROWS).  Returns (w', s') for the selected update ``kind``."""
    rows = w.shape[0]
    grid = (rows // BLOCK_ROWS,)
    spec = pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_BODIES[kind], eta=eta, beta=beta),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(w.shape, w.dtype),
                   jax.ShapeDtypeStruct(s.shape, s.dtype)],
        interpret=interpret,
    )(w, s, delta)


def _pack(leaves):
    """Concatenate flattened leaves (as f32) and pad to the tile grid."""
    flats = [l.astype(jnp.float32).reshape(-1) for l in leaves]
    flat = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
    pad = (-flat.size) % (BLOCK_ROWS * LANE)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANE)


def _unpack(packed, leaves):
    """Slice the updated stream back into the original shapes/dtypes."""
    flat = packed.reshape(-1)
    out, off = [], 0
    for l in leaves:
        out.append(flat[off:off + l.size].reshape(l.shape).astype(l.dtype))
        off += l.size
    return out


def fused_update_tree(w_tree, v_tree, d_tree, *, eta: float, beta: float,
                      interpret: bool, kind: str = "fedmom",
                      fuse_tree: bool = True):
    """Applies the fused update over parameter pytrees.

    Default path: leaves are concatenated into ONE flat stream, padded once
    to the [BLOCK_ROWS, 128] grid, updated in a single kernel launch, and
    sliced back (ragged/bf16/scalar leaves included).  ``fuse_tree=False``
    pads and launches per leaf.
    """
    eta = float(eta)
    beta = float(beta)
    leaves_w, treedef = jax.tree.flatten(w_tree)
    leaves_v = treedef.flatten_up_to(v_tree)
    leaves_d = treedef.flatten_up_to(d_tree)
    if not leaves_w:
        return w_tree, v_tree
    if fuse_tree:
        wn, vn = fused_flat(_pack(leaves_w), _pack(leaves_v),
                            _pack(leaves_d), kind, eta, beta,
                            interpret=interpret)
        out_w = _unpack(wn, leaves_w)
        out_v = _unpack(vn, leaves_v)
    else:
        out_w, out_v = [], []
        for wl, vl, dl in zip(leaves_w, leaves_v, leaves_d):
            wn, vn = fused_flat(_pack([wl]), _pack([vl]), _pack([dl]),
                                kind, eta, beta, interpret=interpret)
            out_w.extend(_unpack(wn, [wl]))
            out_v.extend(_unpack(vn, [vl]))
    return treedef.unflatten(out_w), treedef.unflatten(out_v)
