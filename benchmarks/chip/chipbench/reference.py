"""The plain reference that decides ``correct``: a per-round federated loop.

It follows the program's first chunk of rounds from the same seeds.  Each
round: the sampler part's cohort, client weights and local step counts; the
keyed minibatch draw (H*b indices in [0, n_k) keyed by (minibatch seed, t,
client id)), gathered on the host; each client's local steps of the local
optimiser part on the model's own plain loss; the weighted sum of the
clients' deltas; the server optimiser part's step.  The parts are the
cell's own (``chipbench.parts``), so the reference follows whatever the
configuration names.

It imports nothing of the program and takes nothing the program made (it
builds the initial weights itself from the seed).  A round runs the cohort
in blocks of clients, one jitted call per block: the block's clients side
by side (``jax.vmap``), each a loop of plain steps; no kernels and no
scanned rounds.  The weighted-delta sum and the loss average's numerator
and denominator are summed over the blocks.  The block is the largest
divisor of the cohort whose client replicas (``block_size``) fit
``BLOCK_SHARE`` of the device's memory, so a model at published widths
holds one client at a time where the paper's models hold the whole cohort
and do the arithmetic of a single call.  ``follow`` runs it in
float32 at ``highest`` matmul precision.  With ``dtype=bfloat16`` it is the
control: the configuration states float32, and bfloat16 is the step below.
``fault`` plants one of the faults the check must catch:
``"frozen"`` (the server step returns its state unchanged),
``"half_batch"`` (each local step uses half its minibatch) or
``"no_exchange"`` (on a mesh of ``shards`` chips the cross-chip sum is
left out, so the server steps on the first chip's clients alone).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import parts

FAULTS = ("frozen", "half_batch", "no_exchange")
# a block's client replicas may take this share of the device's memory; the
# rest holds the server state, the running delta sum and the activations
BLOCK_SHARE = 0.5
# float32 words a client replica holds per parameter: its local weights,
# their gradient and its weighted delta
WORDS_PER_PARAM = 3
# leaves whose first aggregated delta is under this share of the median
# leaf's move by round-off alone and are left out of ``change_gap``
STILL_LEAF = 1e-3


class ServerRule(NamedTuple):
    init: Callable[[Any], Any]           # w0 -> state
    step: Callable[[Any, Any], Any]      # (state, delta) -> state
    params: Callable[[Any], Any]         # state -> w


class LocalRule(NamedTuple):
    init: Callable[[Any], Any]                   # p -> state
    # (p, g, state, lr) -> (p, state)
    step: Callable[[Any, Any, Any, Any], Any]


@partial(jax.jit, static_argnums=(4,))
def _draws(key, t, ids, n_k, need):
    def one(cid, n):
        kt = jax.random.fold_in(jax.random.fold_in(key, t), cid)
        return jax.random.randint(kt, (need,), 0, n)
    return jax.vmap(one)(ids, n_k)


def _block(loss_fn, local, half, w, batches, weights, steps, lr, keep):
    """One block of the cohort: the sum of its clients' weighted deltas,
    and the numerator and denominator of the round's loss average.
    ``batches`` leaves are [B, H, b, ...]; client k runs its first
    ``steps[k]`` local steps; ``keep[k]`` = 0 leaves its delta out."""
    H = jax.tree.leaves(batches)[0].shape[1]

    def client(batch, weight, n_steps):
        def step(h, carry):
            p, s, total = carry
            bh = jax.tree.map(lambda a: a[h], batch)
            if half:
                bh = jax.tree.map(lambda a: a[: a.shape[0] // 2], bh)
            loss, g = jax.value_and_grad(loss_fn)(p, bh)
            p2, s2 = local.step(p, g, s, lr)
            on = h < n_steps
            pick = lambda new, old: jax.tree.map(
                lambda a, b: jnp.where(on, a, b), new, old)
            return pick(p2, p), pick(s2, s), total + jnp.where(on, loss, 0)

        zero = jnp.zeros((), lr.dtype)
        p, _, total = jax.lax.fori_loop(0, H, step, (w, local.init(w), zero))
        delta = jax.tree.map(lambda a, b: weight * (a - b), w, p)
        return delta, total / jnp.maximum(n_steps, 1).astype(lr.dtype)

    deltas, losses = jax.vmap(client)(batches, weights, steps)
    delta = jax.tree.map(
        lambda d: jnp.sum(d * keep.reshape((-1,) + (1,) * (d.ndim - 1)), 0),
        deltas)
    live = weights * (steps > 0)
    return delta, jnp.sum(live * losses), jnp.sum(live)


def client_bytes(n_params: int, sample_bytes: int, local_steps: int,
                 local_batch: int) -> int:
    """What one client replica holds: ``WORDS_PER_PARAM`` float32 words
    per parameter, and its H*b samples."""
    return (WORDS_PER_PARAM * 4 * n_params
            + local_steps * local_batch * sample_bytes)


def block_size(cohort: int, per_client: int, memory_bytes) -> int:
    """The largest divisor of ``cohort`` whose clients, ``per_client``
    bytes each, fit ``BLOCK_SHARE`` of ``memory_bytes``; 1 where none
    does, the whole cohort where the memory is unknown (None)."""
    if memory_bytes is None:
        return cohort
    fits = max(1, int(BLOCK_SHARE * memory_bytes) // per_client)
    return max(d for d in range(1, cohort + 1)
               if cohort % d == 0 and d <= fits)


def device_memory():
    """The first device's ``bytes_limit``, or None where the backend does
    not report it (the CPU)."""
    stats = jax.devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def _host(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def _leaf_norms(tree) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in jax.tree.leaves(tree)])


def follow(cell, clients, counts, seeds, rounds: int, dtype=jnp.float32,
           fault=None, shards: int = 1, memory_bytes=None) -> dict:
    """The first ``rounds`` rounds: ``loss`` and ``dnorm`` (the norm of
    the aggregated delta) per round, the per-leaf norms of round 0's
    delta (``delta0``), the initial and final weights (host), and the
    ``block`` of clients run side by side.  ``memory_bytes`` (default:
    the device's, ``device_memory``) sizes the block."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
    model, config, shape = cell.model, cell.config, cell.traffic["cohort"]
    C = shape["clients_per_round"]
    H, b = shape["local_steps"], shape["local_batch"]
    ctx = parts.Ctx(config=config, counts=np.asarray(counts),
                    clients_per_round=C, local_steps=H, local_batch=b,
                    seeds=seeds)
    rules = parts.reference(config, cell.traffic, ctx)
    server, cohort = rules["server_opt"], rules["sampler"]
    lr = jnp.asarray(config["client"]["lr"], dtype)
    keep = np.ones(C, np.float32)
    if fault == "no_exchange":
        keep[C // shards:] = 0
    cast = lambda tree: jax.tree.map(lambda x: x.astype(dtype), tree)
    precision = "highest" if dtype == jnp.float32 else None
    data_key = jax.random.PRNGKey(seeds.minibatch)
    out = {"loss": [], "dnorm": []}
    with jax.default_matmul_precision(precision):
        w0 = cast(jax.jit(lambda k: model.init(k, config["model_config"]))(
            jax.random.PRNGKey(seeds.weights)))
        out["w0"] = _host(w0)
        sample = sum(a[0].nbytes for a in clients[0].values())
        B = block_size(C, client_bytes(
            sum(x.size for x in jax.tree.leaves(w0)), sample, H, b),
            device_memory() if memory_bytes is None else memory_bytes)
        out["block"] = B
        step = jax.jit(partial(_block, model.loss, rules["local_opt"],
                               fault == "half_batch"))
        add = jax.jit(lambda x, y: jax.tree.map(jnp.add, x, y),
                      donate_argnums=0)
        server_step = jax.jit(server.step)
        state = server.init(w0)
        del w0
        for t in range(rounds):
            ids, weights, steps = cohort(t)
            rows = np.asarray(_draws(data_key, t, jnp.asarray(ids),
                                     jnp.asarray(counts[ids]), H * b))
            batches = {name: np.stack([clients[c][name][r] for c, r
                                       in zip(ids, rows)])
                       for name in clients[0]}
            batches = {name: a.reshape((C, H, b) + a.shape[2:])
                       for name, a in batches.items()}
            w, total = server.params(state), None
            for lo in range(0, C, B):
                part = slice(lo, lo + B)
                got = step(w, {name: jnp.asarray(a[part]).astype(
                                   dtype if name in model.FLOAT_FIELDS
                                   else a.dtype)
                               for name, a in batches.items()},
                           jnp.asarray(weights[part], dtype),
                           jnp.asarray(steps[part]), lr,
                           jnp.asarray(keep[part], dtype))
                total = got if total is None else add(total, got)
            delta, num, den = total
            out["loss"].append(float(num / den))
            out["dnorm"].append(float(np.linalg.norm(
                _leaf_norms(_host(delta)))))
            if t == 0:
                out["delta0"] = _leaf_norms(_host(delta))
            if fault != "frozen":
                state = server_step(state, delta)
        out["w"] = _host(server.params(state))
    return out


def gaps(program: dict, ref: dict, loss_rounds: int) -> dict:
    """The numbers a cell's ``limits/<cell>.json`` may compare, program
    against reference:

    * ``loss_gap``, ``dnorm_gap``: the worst relative gap of the round
      loss and of the aggregated delta's norm over the first
      ``loss_rounds`` rounds;
    * ``change_gap``: over the leaves, the worst gap between the norms of
      the weights' change across the followed rounds, over the larger of
      the reference's norm of that leaf and of the median leaf.  Leaves
      whose first delta is under ``STILL_LEAF`` of the median leaf's are
      left out."""
    def rel(p, r):
        g = [abs(a - b) / abs(b) for a, b in zip(p[:loss_rounds],
                                                r[:loss_rounds])]
        return max(x if np.isfinite(x) else np.inf for x in g)

    moved = ref["delta0"] >= STILL_LEAF * np.median(ref["delta0"])
    change = lambda side: _leaf_norms(jax.tree.map(
        lambda a, b: a - b, side["w"], side["w0"]))[moved]
    p, r = change(program), change(ref)
    scale = np.maximum(r, np.median(r))
    worst = np.max(np.abs(p - r) / scale)
    return {"loss_gap": rel(program["loss"], ref["loss"]),
            "dnorm_gap": rel(program["dnorm"], ref["dnorm"]),
            "change_gap": float(worst) if np.isfinite(worst) else np.inf}
