"""Plain SGD on the client (arXiv:2002.02090, Algorithm 2): p -= lr * g."""
import jax

KEYS = set()


def program(spec, ctx):
    return {"rcfg": {"local_opt": "sgd", "local_opt_kwargs": ()}}


def reference(spec, ctx):
    from chipbench.reference import LocalRule

    def step(p, g, state, lr):
        return jax.tree.map(lambda pi, gi: pi - lr * gi, p, g), state

    return LocalRule(init=lambda p: (), step=step)
