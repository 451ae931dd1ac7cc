"""1-layer, 128-unit char-LSTM (arXiv:2002.02090 §5.1) on Shakespeare-
shaped clients.

* ``program_loss`` -- the system under test's model.
* ``init`` / ``loss`` -- the benchmark's own initialiser and a plain float32
  forward and loss for the reference (no program import).
* ``client_sizes`` / ``make_data`` -- the vectorised copy of the program's
  ``synthetic_shakespeare`` + ``lm_clients_to_dataset``: per-role Markov
  chains whose transition rows are 0.5 x a shared Dirichlet(0.15) backbone
  + 0.5 x the role's own.  A role with c characters gives
  max((c - 1) // seq, 1) samples of ``seq`` tokens and their next-char
  labels.  Each sample is drawn as its own chain of seq + 1 characters
  from a uniform first character (the program chops one continuous chain
  per role); the transition statistics are the same.  Sizes come from the
  configuration's size seed; ``seed`` draws the contents.
* ``forward_flops`` -- matmul FLOPs of one sequence's forward pass.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.population import lognormal_sizes


def program_loss():
    from repro.models import small

    return small.lstm_loss


def _dense_init(key, shape, scale=None):
    scale = scale or 1.0 / math.sqrt(shape[0])
    return jax.random.normal(key, shape, jnp.float32) * scale


def init(key, mc: dict):
    v, e, h = mc["vocab"], mc["embed"], mc["hidden"]
    ks = jax.random.split(key, 4)
    return {
        "embed": _dense_init(ks[0], (v, e), scale=0.1),
        "wx": _dense_init(ks[1], (e, 4 * h)),
        "wh": _dense_init(ks[2], (h, 4 * h)),
        "b": jnp.zeros((4 * h,)),
        "head": _dense_init(ks[3], (h, v)),
        "head_b": jnp.zeros((v,)),
    }


def loss(params, batch):
    """Mean next-char NLL.  Steps over time with ``lax.scan`` as the
    program's model does; what the reference leaves out is the round
    engine's scans and vmaps."""
    tokens = batch["tokens"]
    n = tokens.shape[0]
    hidden = params["wh"].shape[0]
    x = params["embed"][tokens]                      # [n, seq, embed]

    def cell(carry, xt):
        h, c = carry
        z = xt @ params["wx"] + h @ params["wh"] + params["b"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    zeros = jnp.zeros((n, hidden), x.dtype)
    _, hs = jax.lax.scan(cell, (zeros, zeros), jnp.swapaxes(x, 0, 1))
    logits = jnp.swapaxes(hs, 0, 1) @ params["head"] + params["head_b"]
    nll = (jax.nn.logsumexp(logits, -1)
           - jnp.take_along_axis(logits, batch["labels"][..., None],
                                 -1)[..., 0])
    return jnp.mean(nll)


FLOAT_FIELDS = ()


def client_sizes(config: dict) -> np.ndarray:
    """Samples per role: max((chars - 1) // seq, 1)."""
    chars = lognormal_sizes(config["population"])
    seq = config["model_config"]["seq_len"]
    return np.maximum((chars - 1) // seq, 1)


@partial(jax.jit, static_argnums=(2, 3, 4))
def _generate(key, client_of, n_clients, vocab, seq):
    kg, ko, k0, kc = jax.random.split(key, 4)
    backbone = jax.random.dirichlet(kg, jnp.full((vocab,), 0.15), (vocab,))
    own = jax.random.dirichlet(ko, jnp.full((vocab,), 0.15),
                               (n_clients, vocab))
    log_t = jnp.log(0.5 * backbone[None] + 0.5 * own)      # [K, V, V]
    first = jax.random.randint(k0, client_of.shape, 0, vocab)

    def step(s, key_t):
        nxt = jax.random.categorical(key_t, log_t[client_of, s])
        return nxt, nxt

    _, rest = jax.lax.scan(step, first, jax.random.split(kc, seq))
    chain = jnp.concatenate([first[None], rest], 0).T.astype(jnp.int32)
    return chain[:, :seq], chain[:, 1:]


def make_data(config: dict, seed: int):
    """(per-client ``{"tokens", "labels": [n_k, seq] i32}`` host views into
    one array, [K] sizes).  Made on the device in one jitted call."""
    mc = config["model_config"]
    counts = client_sizes(config)
    client_of = np.repeat(np.arange(len(counts), dtype=np.int32), counts)
    tok, lab = _generate(jax.random.PRNGKey(seed), jnp.asarray(client_of),
                         len(counts), mc["vocab"], mc["seq_len"])
    tok, lab = np.asarray(tok), np.asarray(lab)
    offs = np.concatenate([[0], np.cumsum(counts)])
    clients = [{"tokens": tok[a:b], "labels": lab[a:b]}
               for a, b in zip(offs[:-1], offs[1:])]
    return clients, counts


def forward_flops(mc: dict) -> float:
    """2 x multiply-adds of the input and recurrent gate matmuls and the
    output head, over the ``seq_len`` steps of one sequence."""
    v, e, h = mc["vocab"], mc["embed"], mc["hidden"]
    return 2.0 * mc["seq_len"] * (e * 4 * h + h * 4 * h + h * v)
