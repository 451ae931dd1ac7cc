"""The paper's own experiment models: LeNet (FEMNIST digit/char recognition,
LeCun et al. 1998) and a 1-layer 128-unit character-level LSTM (Kim et al.
2016) for Shakespeare next-char prediction — §5.1 of the paper.

Pure-function init/apply pairs compatible with the federated round engine
(loss_fn(params, batch) -> (loss, metrics))."""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.data.synthetic import (
    FEMNIST_CLASSES,
    SHAKESPEARE_SEQ,
    SHAKESPEARE_VOCAB,
)


def _dense_init(key, shape, scale=None):
    fan_in = shape[0] if len(shape) == 1 else math.prod(shape[:-1])
    scale = scale or 1.0 / math.sqrt(max(fan_in, 1))
    return jax.random.normal(key, shape, jnp.float32) * scale


# ---------------------------------------------------------------------------
# LeNet
# ---------------------------------------------------------------------------
def lenet_init(key, n_classes: int = FEMNIST_CLASSES):
    ks = jax.random.split(key, 4)
    return {
        "conv1": _dense_init(ks[0], (5, 5, 1, 6)),
        "b1": jnp.zeros((6,)),
        "conv2": _dense_init(ks[1], (5, 5, 6, 16)),
        "b2": jnp.zeros((16,)),
        "fc1": _dense_init(ks[2], (16 * 4 * 4, 120)),
        "bf1": jnp.zeros((120,)),
        "fc2": _dense_init(ks[3], (120, n_classes)),
        "bf2": jnp.zeros((n_classes,)),
    }


def _conv(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def _maxpool(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def lenet_apply(params, x):
    """x [B,28,28,1] -> logits [B,n_classes]."""
    h = jnp.tanh(_conv(x, params["conv1"], params["b1"]))   # 24x24x6
    h = _maxpool(h)                                          # 12x12x6
    h = jnp.tanh(_conv(h, params["conv2"], params["b2"]))   # 8x8x16
    h = _maxpool(h)                                          # 4x4x16
    h = h.reshape(h.shape[0], -1)
    h = jnp.tanh(h @ params["fc1"] + params["bf1"])
    return h @ params["fc2"] + params["bf2"]


def lenet_loss(params, batch):
    logits = lenet_apply(params, batch["x"])
    labels = batch["y"]
    nll = (jax.nn.logsumexp(logits, -1)
           - jnp.take_along_axis(logits, labels[:, None], -1)[:, 0])
    loss = jnp.mean(nll)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"loss": loss, "acc": acc}


# ---------------------------------------------------------------------------
# char-LSTM (1 layer, 128 units, tied 8-dim char embedding per LEAF)
# ---------------------------------------------------------------------------
LSTM_HIDDEN = 128
CHAR_EMBED = 8


def lstm_init(key, vocab: int = SHAKESPEARE_VOCAB,
              hidden: int = LSTM_HIDDEN, embed: int = CHAR_EMBED):
    ks = jax.random.split(key, 4)
    return {
        "embed": _dense_init(ks[0], (vocab, embed), scale=0.1),
        "wx": _dense_init(ks[1], (embed, 4 * hidden)),
        "wh": _dense_init(ks[2], (hidden, 4 * hidden)),
        "b": jnp.zeros((4 * hidden,)),
        "head": _dense_init(ks[3], (hidden, vocab)),
        "head_b": jnp.zeros((vocab,)),
    }


def _lstm_cell(wx, wh, b, h, c, xt):
    """One time step: the activated gates (i, f, g, o), each [B,H], and the
    new (h, c)."""
    z = xt @ wx + h @ wh + b
    i, f, g, o = jnp.split(z, 4, axis=-1)
    i, f = jax.nn.sigmoid(i), jax.nn.sigmoid(f + 1.0)
    g, o = jnp.tanh(g), jax.nn.sigmoid(o)
    c = f * c + i * g
    return (i, f, g, o), o * jnp.tanh(c), c


def _lstm_scan(wx, wh, b, x):
    """x [S,B,E] -> (hs, the four gates, c_prev), each [S,B,H]. h and c
    are float32 whatever the weights' dtype."""
    zeros = jnp.zeros((x.shape[1], wh.shape[0]))

    def step(carry, xt):
        h, c_prev = carry
        gates, h, c = _lstm_cell(wx, wh, b, h, c_prev, xt)
        return (h, c), (h, gates, c_prev)

    _, out = jax.lax.scan(step, (zeros, zeros), x)
    return out


@jax.custom_vjp
def lstm_recurrence(wx, wh, b, x):
    """The LSTM over time: x [S,B,E] -> hs [S,B,H], from h = c = 0.

    Its VJP runs the reverse-time scan with only (dh, dc) in the carry and
    emits each step's gate gradient dz [B,4H]; the weight gradients are
    then one contraction over all S*B rows, not an accumulator of wh's
    shape read and written at every step."""
    return _lstm_scan(wx, wh, b, x)[0]


def _lstm_recurrence_fwd(wx, wh, b, x):
    hs, gates, c_prev = _lstm_scan(wx, wh, b, x)
    return hs, (wx, wh, b, x, hs, gates, c_prev)


def _lstm_recurrence_bwd(res, dhs):
    wx, wh, b, x, hs, gates, c_prev = res
    S = x.shape[0]

    def step(carry, r):
        dh, dc = carry
        t = S - 1 - r
        at = lambda a: jax.lax.dynamic_index_in_dim(a, t, 0, keepdims=False)
        i, f, g, o = (at(a) for a in gates)
        c_prev_t = at(c_prev)
        dh = dh + at(dhs)
        tc = jnp.tanh(f * c_prev_t + i * g)
        dc = dc + dh * o * (1.0 - tc * tc)
        dz = jnp.concatenate([dc * g * i * (1.0 - i),
                              dc * c_prev_t * f * (1.0 - f),
                              dc * i * (1.0 - g * g),
                              dh * tc * o * (1.0 - o)], -1)
        return (dz @ wh.T, dc * f), dz

    zeros = jnp.zeros_like(dhs[0])
    # dz_rev[r] = dz_{S-1-r}: a scan that runs forward writes its stack in
    # place, where a reverse scan would zero-fill it first
    _, dz_rev = jax.lax.scan(step, (zeros, zeros), jnp.arange(S))
    # one contraction over all S*B rows gives dwh, dwx and db: row t of the
    # left side is [h_{t-1}, x_t, 1], in dz_rev's reversed time order
    h_prev = jnp.concatenate([jnp.zeros_like(hs[:1]), hs[:-1]])
    lhs = jnp.concatenate([h_prev, x, jnp.ones_like(x[..., :1])], -1)[::-1]
    rows = lambda a: a.reshape(-1, a.shape[-1])
    grads = rows(lhs).T @ rows(dz_rev)
    H, E = wh.shape[0], wx.shape[0]
    dx = (dz_rev @ wx.T)[::-1]
    return (grads[H:H + E].astype(wx.dtype), grads[:H].astype(wh.dtype),
            grads[H + E].astype(b.dtype), dx.astype(x.dtype))


lstm_recurrence.defvjp(_lstm_recurrence_fwd, _lstm_recurrence_bwd)


def lstm_apply(params, tokens):
    """tokens [B,S] -> logits [B,S,V]."""
    x = params["embed"][tokens].transpose(1, 0, 2)  # [S,B,E]
    hs = lstm_recurrence(params["wx"], params["wh"], params["b"], x)
    return hs.transpose(1, 0, 2) @ params["head"] + params["head_b"]


def lstm_loss(params, batch):
    logits = lstm_apply(params, batch["tokens"])
    labels = batch["labels"]
    nll = (jax.nn.logsumexp(logits, -1)
           - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0])
    loss = jnp.mean(nll)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"loss": loss, "acc": acc}
