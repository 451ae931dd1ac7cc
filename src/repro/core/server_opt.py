"""Server-side federated optimizers — the paper's contribution.

The organizing abstraction follows §3.2 of the paper: model averaging is a
gradient-based method with the *biased gradient*

    delta_t = sum_{k in S_t} (n_k / n) (w_t - w^k_{t+1})        (eq. 3)

Every server optimizer consumes ``delta_t`` (fp32, already aggregated across
clients) and produces the next server state.  This is exactly why the paper's
reformulation matters: once averaging is a gradient step, *any* gradient
method lifts to the server.  FedAvg and FedMom are paper-faithful; FedAvgM,
FedAdam, FedYogi and FedLaMom are beyond-paper members of the same family
(kept here to demonstrate the abstraction the paper opens up).

FedSGD is not a separate optimizer: it is FedAvg with H=1 local steps (one
local SGD step of size gamma makes delta_t = gamma * avg-grad; see
tests/test_server_opt.py::test_fedsgd_equivalence).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp


class ServerState(NamedTuple):
    w: Any                 # master params, fp32
    extra: Any             # optimizer-specific state (pytree or ())
    t: jax.Array           # round counter


def _tmap(f, *trees):
    return jax.tree.map(f, *trees)


def _zeros_like_f32(w):
    return _tmap(lambda x: jnp.zeros(x.shape, jnp.float32), w)


@dataclass(frozen=True)
class ServerOpt:
    name: str
    init_extra: Callable[[Any], Any]
    apply: Callable[[Any, Any, Any, jax.Array], tuple]
    # (w, extra, delta, t) -> (w', extra')

    def init(self, w0) -> ServerState:
        # Always copy: the scanned driver donates ServerState buffers, and
        # astype(float32) on an already-f32 leaf would alias the caller's w0
        # (whose buffers would then be deleted by the first donated chunk).
        w0 = _tmap(lambda x: jnp.array(x, jnp.float32), w0)
        return ServerState(w=w0, extra=self.init_extra(w0),
                           t=jnp.zeros((), jnp.int32))

    def update(self, state: ServerState, delta) -> ServerState:
        delta = _tmap(lambda d: d.astype(jnp.float32), delta)
        w, extra = self.apply(state.w, state.extra, delta, state.t)
        return ServerState(w=w, extra=extra, t=state.t + 1)


# ---------------------------------------------------------------------------
# paper-faithful
# ---------------------------------------------------------------------------
def fedavg(eta: float = 1.0) -> ServerOpt:
    """Algorithm 1.  eta in [1, K/M] (eq. generalizing (3)); eta=1 is exact
    model averaging (eq. 2 == eq. 3)."""
    def apply(w, extra, delta, t):
        return _tmap(lambda wi, di: wi - eta * di, w, delta), extra
    return ServerOpt("fedavg", lambda w: (), apply)


def fedmom(eta: float = 1.0, beta: float = 0.9, *,
           use_fused_kernel: bool = False) -> ServerOpt:
    """Algorithm 3 (FedMom): Nesterov's accelerated gradient on the server.

        v_{t+1} = w_t - eta * delta_t
        w_{t+1} = v_{t+1} + beta (v_{t+1} - v_t)

    beta=0.9 everywhere in the paper's experiments.  ``use_fused_kernel``
    routes the elementwise update through the Pallas kernel
    (kernels/fedmom_update) — one HBM pass instead of three ops.  A
    program jitted by hand traces it under ``kernel_platform(...)``
    (``FederatedTrainer.run`` names the platform itself; see
    ``kernels/_device.py``).
    """
    def init_extra(w):
        return {"v": jax.tree.map(jnp.copy, w)}   # v_0 = w_0

    def apply(w, extra, delta, t):
        if use_fused_kernel:
            from repro.kernels import fedmom_ops
            w_new, v_new = fedmom_ops.fused_update_tree(
                w, extra["v"], delta, eta=eta, beta=beta)
            return w_new, {"v": v_new}
        v_new = _tmap(lambda wi, di: wi - eta * di, w, delta)
        w_new = _tmap(lambda vn, vo: vn + beta * (vn - vo), v_new, extra["v"])
        return w_new, {"v": v_new}

    return ServerOpt("fedmom", init_extra, apply)


# ---------------------------------------------------------------------------
# beyond-paper members of the biased-gradient family
# ---------------------------------------------------------------------------
def fedavgm(eta: float = 1.0, beta: float = 0.9, *,
            use_fused_kernel: bool = False) -> ServerOpt:
    """Heavy-ball (Polyak) server momentum on the biased gradient.

    ``use_fused_kernel`` routes the update through the fused Pallas stream
    (kernels/fedmom_update, ``kind='fedavgm'``) — one HBM pass over the
    whole parameter tree instead of two unfused tree ops.
    """
    def apply(w, extra, delta, t):
        if use_fused_kernel:
            from repro.kernels import fedmom_ops
            w_new, m_new = fedmom_ops.fused_avgm_tree(
                w, extra["m"], delta, eta=eta, beta=beta)
            return w_new, {"m": m_new}
        m = _tmap(lambda mi, di: beta * mi + di, extra["m"], delta)
        return _tmap(lambda wi, mi: wi - eta * mi, w, m), {"m": m}
    return ServerOpt("fedavgm", lambda w: {"m": _zeros_like_f32(w)}, apply)


def fedadam(eta: float = 0.1, b1: float = 0.9, b2: float = 0.99,
            tau: float = 1e-3) -> ServerOpt:
    """Adaptive server optimizer (Reddi et al. 2021) on the biased gradient."""
    def apply(w, extra, delta, t):
        m = _tmap(lambda mi, di: b1 * mi + (1 - b1) * di, extra["m"], delta)
        v = _tmap(lambda vi, di: b2 * vi + (1 - b2) * jnp.square(di),
                  extra["v"], delta)
        w = _tmap(lambda wi, mi, vi: wi - eta * mi / (jnp.sqrt(vi) + tau),
                  w, m, v)
        return w, {"m": m, "v": v}
    return ServerOpt(
        "fedadam",
        lambda w: {"m": _zeros_like_f32(w), "v": _zeros_like_f32(w)},
        apply)


def fedyogi(eta: float = 0.1, b1: float = 0.9, b2: float = 0.99,
            tau: float = 1e-3) -> ServerOpt:
    def apply(w, extra, delta, t):
        m = _tmap(lambda mi, di: b1 * mi + (1 - b1) * di, extra["m"], delta)
        v = _tmap(
            lambda vi, di: vi - (1 - b2) * jnp.square(di)
            * jnp.sign(vi - jnp.square(di)),
            extra["v"], delta)
        w = _tmap(lambda wi, mi, vi: wi - eta * mi
                  / (jnp.sqrt(jnp.maximum(vi, 0.0)) + tau), w, m, v)
        return w, {"m": m, "v": v}
    return ServerOpt(
        "fedyogi",
        lambda w: {"m": _zeros_like_f32(w), "v": _zeros_like_f32(w)},
        apply)


def fedlamom(eta: float = 1.0, beta: float = 0.9) -> ServerOpt:
    """Our layerwise-damped Nesterov variant: FedMom with a per-tensor
    trust ratio min(1, ||w|| / ||update||).  Heterogeneous clients produce
    very unequal per-layer delta magnitudes; the damping caps any layer's
    step at its own parameter norm (never amplifies), which tames the
    occasional exploding layer without touching well-behaved ones."""
    def init_extra(w):
        return {"v": jax.tree.map(jnp.copy, w)}

    def apply(w, extra, delta, t):
        def upd_w(wi, vi, di):
            v_new = wi - eta * di
            raw = v_new + beta * (v_new - vi) - wi
            wn = jnp.linalg.norm(wi.reshape(-1))
            un = jnp.linalg.norm(raw.reshape(-1))
            trust = jnp.minimum(1.0, wn / (un + 1e-12))
            trust = jnp.where(wn > 0, trust, 1.0)
            return wi + trust * raw

        v_new = _tmap(lambda wi, di: wi - eta * di, w, delta)
        w_new = _tmap(upd_w, w, extra["v"], delta)
        return w_new, {"v": v_new}

    return ServerOpt("fedlamom", init_extra, apply)


# ---------------------------------------------------------------------------
# central differential privacy: clip + seeded Gaussian noise on delta_t
# ---------------------------------------------------------------------------
def dp(inner: ServerOpt, clip: float = 1.0,
       noise_multiplier: float = 0.0, seed: int = 0) -> ServerOpt:
    """Central-DP wrapper: before ``inner`` consumes the aggregate, clip
    delta_t to global L2 norm ``clip`` and add per-coordinate Gaussian
    noise N(0, (clip * noise_multiplier)^2).

    The noise is a pure function of ``(seed, t)`` — key
    ``fold_in(PRNGKey(seed), t)``, folded once more per tree leaf — so a
    DP trajectory is plane-independent and resumable exactly like the
    noiseless ones (the trajectory tests assert seeded-noise equivalence
    across all execution planes, not approximate statistics).

    Trust-model note: this is *central* DP (the Gaussian mechanism applied
    to the aggregate), the composition that makes sense with secure
    aggregation — the server never sees individual updates, so per-client
    clipping (local-DP FedAvg à la McMahan et al. 2018) is not available
    to it; the clip here bounds the whole round's sensitivity instead.
    """
    if clip <= 0:
        raise ValueError(f"dp clip must be > 0, got {clip!r}")
    if noise_multiplier < 0:
        raise ValueError(
            f"dp noise_multiplier must be >= 0, got {noise_multiplier!r}")

    def apply(w, extra, delta, t):
        norm = jnp.sqrt(sum(
            jnp.sum(jnp.square(d.astype(jnp.float32)))
            for d in jax.tree.leaves(delta)))
        factor = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
        clipped = _tmap(lambda d: factor * d.astype(jnp.float32), delta)
        if noise_multiplier > 0:
            key_t = jax.random.fold_in(jax.random.PRNGKey(seed), t)
            sigma = clip * noise_multiplier
            leaves, treedef = jax.tree.flatten(clipped)
            noisy = [
                l + sigma * jax.random.normal(
                    jax.random.fold_in(key_t, i), l.shape, jnp.float32)
                for i, l in enumerate(leaves)]
            clipped = jax.tree.unflatten(treedef, noisy)
        return inner.apply(w, extra, clipped, t)

    return ServerOpt(f"dp_{inner.name}", inner.init_extra, apply)


def dp_fedavg(clip: float = 1.0, noise_multiplier: float = 0.0,
              dp_seed: int = 0, **inner_kw) -> ServerOpt:
    """DP-FedAvg: central clip + seeded Gaussian noise around ``fedavg``."""
    return dp(fedavg(**inner_kw), clip, noise_multiplier, dp_seed)


def dp_fedmom(clip: float = 1.0, noise_multiplier: float = 0.0,
              dp_seed: int = 0, **inner_kw) -> ServerOpt:
    """DP-FedMom: central clip + seeded Gaussian noise around ``fedmom``
    (the paper's Nesterov server momentum on a privatized delta_t)."""
    return dp(fedmom(**inner_kw), clip, noise_multiplier, dp_seed)


REGISTRY: Dict[str, Callable[..., ServerOpt]] = {
    "fedavg": fedavg,
    "fedmom": fedmom,
    "fedavgm": fedavgm,
    "fedadam": fedadam,
    "fedyogi": fedyogi,
    "fedlamom": fedlamom,
    "dp_fedavg": dp_fedavg,
    "dp_fedmom": dp_fedmom,
}


def get(name: str, **kw) -> ServerOpt:
    return REGISTRY[name](**kw)
