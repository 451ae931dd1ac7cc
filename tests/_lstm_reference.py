"""The char-LSTM as plain ``lax.scan`` autodiff sees it: the reference for
``repro.models.small.lstm_loss``, whose recurrence has its own VJP.

The reverse scan autodiff builds from this cell carries the gradient of
``wh`` and adds ``h_{t-1}^T dz_t`` into it at every time step."""
import jax
import jax.numpy as jnp


def lstm_apply(params, tokens):
    """tokens [B,S] -> logits [B,S,V]."""
    B, S = tokens.shape
    H = params["wh"].shape[0]
    x = params["embed"][tokens]                    # [B,S,E]

    def cell(carry, xt):
        h, c = carry
        z = xt @ params["wx"] + h @ params["wh"] + params["b"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    h0 = (jnp.zeros((B, H)), jnp.zeros((B, H)))
    _, hs = jax.lax.scan(cell, h0, x.transpose(1, 0, 2))
    hs = hs.transpose(1, 0, 2)                     # [B,S,H]
    return hs @ params["head"] + params["head_b"]


def lstm_loss(params, batch):
    logits = lstm_apply(params, batch["tokens"])
    labels = batch["labels"]
    nll = (jax.nn.logsumexp(logits, -1)
           - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0])
    loss = jnp.mean(nll)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"loss": loss, "acc": acc}
