"""LeNet (arXiv:2002.02090 §5.1) on FEMNIST-shaped clients.

* ``program_loss`` -- the system under test's model.
* ``init`` / ``loss`` -- the benchmark's own copy of the initialiser and a
  plain float32 forward and loss for the reference (no program import).
* ``client_sizes`` / ``make_data`` -- the vectorised copy of the program's
  ``synthetic_femnist``: class prototypes (N(0,1) blobs smoothed by a 3x3
  box filter with edge padding), a Dirichlet(0.3) label prior per writer,
  a N(0, 0.6) style offset per writer and N(0, 0.35) pixel noise.  Sizes
  come from the configuration's size seed; ``seed`` draws the contents.
* ``forward_flops`` -- convolution and matmul FLOPs of one sample's
  forward pass, from the shapes.
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

    return small.lenet_loss


def _dense_init(key, shape):
    fan_in = shape[0] if len(shape) == 1 else math.prod(shape[:-1])
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def init(key, mc: dict):
    k1, k2 = mc["conv1"], mc["conv2"]
    flat = mc["pooled_hw"] ** 2 * k2[3]
    ks = jax.random.split(key, 4)
    return {
        "conv1": _dense_init(ks[0], tuple(k1)),
        "b1": jnp.zeros((k1[3],)),
        "conv2": _dense_init(ks[1], tuple(k2)),
        "b2": jnp.zeros((k2[3],)),
        "fc1": _dense_init(ks[2], (flat, mc["fc1"])),
        "bf1": jnp.zeros((mc["fc1"],)),
        "fc2": _dense_init(ks[3], (mc["fc1"], mc["classes"])),
        "bf2": jnp.zeros((mc["classes"],)),
    }


def _dot(a, w):
    """a [..., K] x w [K, N] as a broadcast product summed over K: every
    multiply and add in the array's own dtype on the vector units, so the
    float32 reference is exact float32 whatever the matmul precision, and
    compiles in seconds (an emulated float32 convolution or matmul at
    ``highest`` precision takes minutes to compile for the TPU)."""
    return jnp.sum(a[..., :, None] * w, axis=-2)


def _conv(x, w, b):
    """VALID, stride-1 NHWC x HWIO convolution as shifted slices and
    ``_dot``."""
    kh, kw, cin, cout = w.shape
    oh, ow = x.shape[1] - kh + 1, x.shape[2] - kw + 1
    patches = jnp.stack([x[:, i:i + oh, j:j + ow, :]
                         for i in range(kh) for j in range(kw)], axis=3)
    return _dot(patches.reshape(x.shape[0], oh, ow, kh * kw * cin),
                w.reshape(kh * kw * cin, cout)) + b


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def loss(params, batch):
    h = _pool(jnp.tanh(_conv(batch["x"], params["conv1"], params["b1"])))
    h = _pool(jnp.tanh(_conv(h, params["conv2"], params["b2"])))
    h = h.reshape(h.shape[0], -1)
    h = jnp.tanh(_dot(h, params["fc1"]) + params["bf1"])
    logits = _dot(h, params["fc2"]) + params["bf2"]
    nll = (jax.nn.logsumexp(logits, -1)
           - jnp.take_along_axis(logits, batch["y"][:, None], -1)[:, 0])
    return jnp.mean(nll)


FLOAT_FIELDS = ("x",)


def client_sizes(config: dict) -> np.ndarray:
    return lognormal_sizes(config["population"])


@partial(jax.jit, static_argnums=(2, 3, 4))
def _generate(key, client_of, n_clients, hw, classes):
    """Images as [N, hw * hw] rows: a trailing axis of 28 would be padded
    to 128 lanes in the TPU's layout and take 4.6x the memory."""
    kp, kd, kl, ks, kn = jax.random.split(key, 5)
    protos = jax.random.normal(kp, (classes, hw, hw))
    padded = jnp.pad(protos, ((0, 0), (1, 1), (1, 1)), mode="edge")
    protos = sum(padded[:, i:i + hw, j:j + hw]
                 for i in range(3) for j in range(3)) / 9.0
    prior = jax.random.dirichlet(kd, jnp.full((classes,), 0.3),
                                 (n_clients,))
    labels = jax.random.categorical(kl, jnp.log(prior)[client_of])
    style = 0.6 * jax.random.normal(ks, (n_clients, hw * hw))
    noise = 0.35 * jax.random.normal(kn, (client_of.shape[0], hw * hw))
    x = protos.reshape(classes, hw * hw)[labels] + style[client_of] + noise
    return x, labels.astype(jnp.int32)


def make_data(config: dict, seed: int):
    """(per-client ``{"x": [n_k, 28, 28, 1] f32, "y": [n_k] i32}`` host
    views into one array, [K] sizes).  Made on the device in one jitted
    call and copied to the host, which is where the program takes data."""
    mc = config["model_config"]
    hw = mc["input_hw"]
    counts = client_sizes(config)
    client_of = np.repeat(np.arange(len(counts), dtype=np.int32), counts)
    x, y = _generate(jax.random.PRNGKey(seed), jnp.asarray(client_of),
                     len(counts), hw, mc["classes"])
    x = np.asarray(x).reshape(-1, hw, hw, 1)
    y = np.asarray(y)
    offs = np.concatenate([[0], np.cumsum(counts)])
    clients = [{"x": x[a:b], "y": y[a:b]} for a, b in zip(offs[:-1], offs[1:])]
    return clients, counts


def forward_flops(mc: dict) -> float:
    """2 x multiply-adds of the two convolutions and two dense layers for
    one 28x28 sample (pooling, tanh and the softmax are not counted)."""
    hw = mc["input_hw"]
    k1, k2 = mc["conv1"], mc["conv2"]
    o1 = hw - k1[0] + 1                           # 24
    o2 = o1 // 2 - k2[0] + 1                      # 8
    macs = (o1 * o1 * k1[3] * k1[0] * k1[1] * k1[2]
            + o2 * o2 * k2[3] * k2[0] * k2[1] * k2[2]
            + mc["pooled_hw"] ** 2 * k2[3] * mc["fc1"]
            + mc["fc1"] * mc["classes"])
    return 2.0 * macs
