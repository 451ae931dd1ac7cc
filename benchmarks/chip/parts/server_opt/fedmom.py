"""FedMom (arXiv:2002.02090, Algorithm 3): Nesterov momentum on the server.

    v' = w - eta * delta,    w' = v' + beta * (v' - v),    v_0 = w_0

``eta`` is a number or ``"K/C"`` (the population over the cohort);
``fused_kernel`` routes the program's update through its Pallas kernel.
"""
import jax

KEYS = {"eta", "beta", "fused_kernel"}


def _eta(spec, ctx) -> float:
    if spec["eta"] == "K/C":
        return len(ctx.counts) / ctx.clients_per_round
    return float(spec["eta"])


def program(spec, ctx):
    from repro.core import fedmom

    return {"trainer": {"server_opt": fedmom(
        eta=_eta(spec, ctx), beta=spec["beta"],
        use_fused_kernel=spec["fused_kernel"])}}


def reference(spec, ctx):
    from chipbench.reference import ServerRule

    eta, beta = _eta(spec, ctx), spec["beta"]

    def step(state, delta):
        w, v = state
        v_new = jax.tree.map(lambda wi, di: wi - eta * di, w, delta)
        w_new = jax.tree.map(lambda vn, vo: vn + beta * (vn - vo), v_new, v)
        return w_new, v_new

    return ServerRule(init=lambda w: (w, w), step=step,
                      params=lambda state: state[0])
