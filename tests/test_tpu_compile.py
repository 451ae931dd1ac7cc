"""The main path compiles for a TPU v5e chip that is described, not attached.

Mosaic (the TPU kernel compiler) refuses things interpret mode accepts —
misaligned block shapes, vector loads of scalars — so each main-path
kernel, and the LeNet round program that calls the fused server update, is
compiled here at real widths for one chip of a described ``v5e:2x2``
topology.  Every compiled text must carry ``tpu_custom_call``: the kernel
was compiled, not interpreted.  The char-LSTM's local update is compiled
the same way, to read which tensors its time-step loops carry.  Nothing
runs; a compile is not a chip run.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import _lstm_reference
from repro.core import RoundConfig, fedmom, round_step
from repro.core.client import local_update
from repro.kernels._device import kernel_platform
from repro.kernels.client_step import kernel as cs_kernel
from repro.kernels.fedmom_update import kernel as fm_kernel
from repro.models import small


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _lenet_shapes():
    return jax.eval_shape(small.lenet_init, jax.random.PRNGKey(0))


def _packed_rows(n_params):
    tile = fm_kernel.BLOCK_ROWS * fm_kernel.LANE
    return -(-n_params // tile) * fm_kernel.BLOCK_ROWS


def _assert_compiled_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("stream", ["lenet", "1m"])
@pytest.mark.parametrize("kind", ["fedmom", "fedavgm"])
def test_fused_server_update_compiles(one_chip, stream, kind):
    n_params = (sum(x.size for x in jax.tree.leaves(_lenet_shapes()))
                if stream == "lenet" else 1_000_000)
    x = _sds((_packed_rows(n_params), fm_kernel.LANE), one_chip)
    _assert_compiled_kernel(
        lambda w, s, d: fm_kernel.fused_flat(w, s, d, kind, 7.8125, 0.9,
                                             interpret=False), x, x, x)


@pytest.mark.parametrize("C,S,N,H,b", [
    (64, 256, 64, 10, 4),    # a Zipf fleet's widest tier (n_k <= 64)
    (8, 64, 8, 10, 4),       # its smallest (n_k 4..8)
])
def test_client_step_compiles(one_chip, C, S, N, H, b):
    Dp = cs_kernel.LANE                  # dim 16 padded to the lane width
    i32 = jnp.int32
    _assert_compiled_kernel(
        lambda *a: cs_kernel.client_step_flat(
            *a, local_steps=H, batch_size=b, interpret=False),
        _sds((S, N, Dp), one_chip), _sds((S, N, 1), one_chip),
        _sds((C,), one_chip, i32), _sds((C, H * b), one_chip, i32),
        _sds((1, Dp), one_chip), _sds((1, 1), one_chip),
        _sds((1, 1), one_chip), _sds((C, H), one_chip))


def test_lenet_round_step_compiles(one_chip):
    """The jitted LeNet round (64 clients x 10 local steps of batch 10)
    with the fused FedMom server update, traced for the chip as the device
    plane traces it."""
    C, H, b = 64, 10, 10
    opt = fedmom(eta=500 / C, beta=0.9, use_fused_kernel=True)
    rcfg = RoundConfig(clients_per_round=C, local_steps=H, lr=0.05,
                       compute_dtype="float32")
    state = jax.tree.map(lambda s: _sds(s.shape, one_chip, s.dtype),
                         jax.eval_shape(opt.init, _lenet_shapes()))
    batches = {"x": _sds((C, H, b, 28, 28, 1), one_chip),
               "y": _sds((C, H, b), one_chip, jnp.int32)}
    with kernel_platform("tpu"):
        _assert_compiled_kernel(
            lambda st, bt, w: round_step(small.lenet_loss, opt, st, bt, w,
                                         rcfg),
            state, batches, _sds((C,), one_chip))


def _time_step_loop_carries(hlo_text):
    """The carried shapes of each ``while`` whose body holds no other
    ``while``: the time-step loops, nested in the local-step loop."""
    bodies = {}
    name = None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)
    loops = [re.search(r"= \((.*)\) while\(.*body=%([^,\s]+)", line)
             for lines in bodies.values() for line in lines]
    return [m.group(1) for m in loops if m and not any(
        " while(" in line for line in bodies[m.group(2)])]


@pytest.mark.parametrize("loss_name", ["lstm_loss", "reference"])
def test_lstm_time_step_loops_carry_no_wh_gradient(one_chip, loss_name):
    """The cohort's char-LSTM local update (C = 8 clients, H = 10 local
    steps of b = 10 sequences of 80 tokens) compiled for the chip: neither
    time-step loop of ``small.lstm_loss`` carries a per-client f32
    [C, 128, 512] tensor (wh's gradient is formed after the reverse scan),
    where autodiff of the plain scan cell accumulates it in its reverse
    loop."""
    C, H, b, S = 8, 10, 10, 80
    loss = (small.lstm_loss if loss_name == "lstm_loss"
            else _lstm_reference.lstm_loss)
    params = jax.tree.map(
        lambda s: _sds((C,) + s.shape, one_chip, s.dtype),
        jax.eval_shape(small.lstm_init, jax.random.PRNGKey(0)))
    tokens = _sds((C, H, b, S), one_chip, jnp.int32)
    text = jax.jit(jax.vmap(
        lambda p, bt: local_update(loss, p, bt, 0.05))).lower(
            params, {"tokens": tokens, "labels": tokens}).compile().as_text()
    carries = _time_step_loop_carries(text)
    assert len(carries) == 2                 # the forward and reverse scans
    wh_grad = [c for c in carries if "f32[8,128,512]" in c]
    assert len(wh_grad) == (0 if loss_name == "lstm_loss" else 1)
