"""Smoke run of FedMom training on a TPU, end to end, in one process.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

This is a smoke test, not a benchmark: the wall and compile times it
prints are for orientation only.  Everything runs through the user entry
point ``FederatedTrainer.run(n, plan=ExecutionPlan(...))``; data and
weights are synthetic, made from fixed seeds.

* Phase A — LeNet (paper widths: 62 classes, 28x28x1) on FEMNIST-shaped
  clients, 500 clients, 64 per round, H=10 local steps of batch 10, on the
  ``device`` plane: FedMom with the fused Pallas server-update kernel vs
  the same 20 rounds through XLA.  Asserts finite losses, agreement, and
  ``tpu_custom_call`` (the compiled kernel) in the round program.
* Phase B — a 10k-client Zipf linear-regression fleet (dim 16, n_k 4..64)
  on the bucketed ``streaming`` plane, with the fused Pallas client-step
  kernel vs the XLA local update; same three assertions.
* ``--chips 4`` — Phase A's model and data on the ``device`` and
  ``streaming`` planes under ``ExecutionPlan(mesh=MeshSpec(devices=4))``
  vs the same runs on one chip, and checks that the cohort really splits
  across the four devices.  Both planes run and print their comparisons
  before any failure is raised.

LeNet's trajectories are compared against a floor measured in the same
process: the same run with the cohort listed in reverse, which changes
nothing but the order the round sums its clients.  Local SGD grows any
such one-ulp difference round by round (more at the TPU's default matmul
precision, where a float32 convolution takes bf16 passes), so two correct
implementations drift apart by about as much as that reordering does; a
wrong kernel or a wrong reduction misses it by orders.

Exits non-zero, printing no result line, unless JAX's first device is a
TPU, and whenever any phase fails.  The last line of standard output is
one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

ROUNDS, CHUNK = 20, 10
# LeNet comparisons: an arm may drift from its reference by at most
# FLOOR_FACTOR times the drift the reversed-cohort run shows (see the
# module docstring), per metric: max |loss diff| over the rounds, and the
# final parameters' relative L2 difference.  DRIFT_MIN keeps the bound
# meaningful should the reordering happen to change no bit at all.
FLOOR_FACTOR = 10.0
DRIFT_MIN = 1e-6
# Phase A, rounds 0-1: ~20 ulp at LeNet's initial loss (~4)
ATOL_KERNEL_HEAD = 1e-5
# Phase B: hand-fused gradients (exact fp32 VPU work) vs AD at float32
# matmul precision; linear regression does not amplify, so the whole
# trajectory is held to tests/test_bucketed.py's tolerance
ATOL_CLIENT_STEP = 1e-5
# --chips 4, round 0 (before any aggregation has moved the weights):
# tests/test_mesh_shard.py's tolerance
ATOL_MESH_HEAD = 1e-6


class _CompileClock:
    """Seconds XLA spent compiling while active (JAX monitoring events)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self._event = "/jax/core/compile/backend_compile_duration"
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self._event:
            self.seconds += duration


@functools.lru_cache(maxsize=None)
def _femnist(n_clients, seed):
    from repro.data import synthetic_femnist

    return synthetic_femnist(n_clients=n_clients, seed=seed)[0]


def _lenet_trainer(opt, n_clients=500, m=64, local_steps=10, lr=0.05,
                   local_batch=10, seed=0, reverse_cohort=False):
    import jax

    from repro.core import DeviceUniformSampler, RoundConfig
    from repro.data import FederatedDataset
    from repro.launch.train import FederatedTrainer
    from repro.models import small

    class ReversedCohort(DeviceUniformSampler):
        """The same clients each round, listed in reverse."""

        def sample_device(self, key, t):
            idx, w = super().sample_device(key, t)
            return idx[::-1], w[::-1]

    ds = FederatedDataset(_femnist(n_clients, seed), seed=seed + 7)
    rcfg = RoundConfig(clients_per_round=m, local_steps=local_steps, lr=lr,
                       compute_dtype="float32")
    sampler = ReversedCohort if reverse_cohort else DeviceUniformSampler
    return FederatedTrainer(
        loss_fn=small.lenet_loss, server_opt=opt, rcfg=rcfg, dataset=ds,
        sampler=sampler(ds.population(), m, seed=seed),
        state=opt.init(small.lenet_init(jax.random.PRNGKey(seed))),
        local_batch=local_batch)


def _linreg_loss(params, batch):
    import jax.numpy as jnp

    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean(jnp.square(pred - batch["y"])), {}


def _zipf_trainer(client_step_fn, n_clients=10_000, dim=16, m=64,
                  local_steps=10, local_batch=4, seed=0):
    import jax.numpy as jnp

    from repro.core import DeviceUniformSampler, RoundConfig, fedmom
    from repro.data import StreamingFederatedDataset
    from repro.launch.train import FederatedTrainer
    from repro.scenario import zipf_linreg_provider

    provider = zipf_linreg_provider(n_clients, dim=dim, n_min=4, n_max=64,
                                    seed=seed)
    ds = StreamingFederatedDataset.from_provider(provider, seed=seed + 7)
    opt = fedmom(eta=n_clients / m, beta=0.9)
    rcfg = RoundConfig(clients_per_round=m, local_steps=local_steps, lr=0.05,
                       compute_dtype="float32")
    return FederatedTrainer(
        loss_fn=_linreg_loss, server_opt=opt, rcfg=rcfg, dataset=ds,
        sampler=DeviceUniformSampler(ds.population(), m, seed=seed),
        state=opt.init({"w": jnp.zeros(dim), "b": jnp.zeros(())}),
        local_batch=local_batch, client_step_fn=client_step_fn)


def _losses(hist):
    return np.asarray([r["loss"] for r in hist if "event" not in r])


def _flat(tree):
    import jax

    return np.concatenate([np.ravel(np.asarray(x))
                           for x in jax.tree.leaves(tree)])


def _run(tr, plan, clock, label):
    """One timed ``run``; returns (losses, final params)."""
    import jax

    c0, t0 = clock.seconds, time.perf_counter()
    losses = _losses(tr.run(ROUNDS, plan=plan, verbose=False))
    jax.block_until_ready(tr.state.w)
    wall = time.perf_counter() - t0
    print(f"  {label}: wall {wall:.3f} s (compile {clock.seconds - c0:.3f} "
          f"s), losses "
          f"{np.array2string(losses, precision=6, max_line_width=10**6)}")
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{label}: non-finite losses {losses}")
    return losses, _flat(tr.state.w)


def _drift(got, want):
    """(max |loss diff| over the rounds, final params relative L2 diff)
    between two (losses, final params) runs."""
    (lg, wg), (lw, ww) = got, want
    return (float(np.max(np.abs(lg - lw))),
            float(np.linalg.norm(wg - ww) / np.linalg.norm(ww)))


def _agreement_failures(got, want, floor_run, label, head_rounds,
                        head_atol):
    """Compare ``got`` with ``want`` (the reference) and print it; return
    the failures.  The first ``head_rounds`` losses must agree within
    ``head_atol``; over the whole run each drift metric must stay within
    FLOOR_FACTOR times the reversed-cohort run's drift from ``want``."""
    drift, floor = _drift(got, want), _drift(floor_run, want)
    head = np.abs(got[0][:head_rounds] - want[0][:head_rounds])
    print(f"  {label}: |loss diff| per round "
          f"{np.array2string(np.abs(got[0] - want[0]), precision=2, max_line_width=10**6)}"
          f"; max {drift[0]:.3e} (reversed cohort {floor[0]:.3e}), "
          f"final params relative L2 {drift[1]:.3e} "
          f"(reversed cohort {floor[1]:.3e})")
    failures = []
    if not np.all(head <= head_atol):
        failures.append(f"{label}: first {head_rounds} round(s) differ by "
                        f"{head.max():.3e} > {head_atol:g}")
    for name, d, f in zip(("max |loss diff|", "final params relative L2"),
                          drift, floor):
        bound = FLOOR_FACTOR * max(f, DRIFT_MIN)
        if not d <= bound:
            failures.append(f"{label}: {name} {d:.3e} > {bound:.3e} "
                            f"({FLOOR_FACTOR:g} x the reversed cohort's)")
    return failures


def _compiled_text(fn, *args):
    """Compiled text of a trainer's jitted chunk for these arguments,
    traced for the platform the trainer places on."""
    import jax

    from repro.kernels._device import kernel_platform

    with kernel_platform(jax.devices()[0].platform):
        return fn.lower(*args).compile().as_text()


def _assert_kernel(text, label):
    if "tpu_custom_call" not in text:
        raise AssertionError(
            f"{label}: no tpu_custom_call in the compiled round program — "
            f"the Pallas kernel was not compiled for the chip")
    print(f"  {label}: tpu_custom_call present in the compiled chunk")


def _raise_if(failures):
    if failures:
        raise AssertionError("; ".join(failures))


def phase_a(clock):
    """LeNet on FEMNIST-shaped clients: fused FedMom kernel vs XLA."""
    import jax.numpy as jnp

    from repro.core import fedmom
    from repro.launch.plan import ExecutionPlan

    print("phase A: LeNet / FEMNIST-shaped, device plane, fused FedMom "
          "server update vs XLA")
    plan = ExecutionPlan(plane="device", chunk_rounds=CHUNK)
    eta = 500 / 64                                   # K / M
    runs = {}
    for arm, fused, reverse in (("kernel", True, False),
                                ("xla", False, False),
                                ("kernel, reversed cohort", True, True)):
        tr = _lenet_trainer(fedmom(eta=eta, beta=0.9, use_fused_kernel=fused),
                            reverse_cohort=reverse)
        runs[arm] = _run(tr, plan, clock, arm)
        if arm == "kernel":
            dds = tr.device_dataset()
            text = _compiled_text(
                tr._device_chunk_fn(CHUNK, False), tr.state, dds,
                tr._sample_key(), dds.base_key(), jnp.int32(0),
                jnp.full((CHUNK,), tr.rcfg.lr, jnp.float32))
            _assert_kernel(text, "kernel")
    _raise_if(_agreement_failures(
        runs["xla"], runs["kernel"], runs["kernel, reversed cohort"],
        "xla vs kernel", 2, ATOL_KERNEL_HEAD))


def phase_b(clock):
    """Zipf linreg fleet, bucketed streaming: fused client step vs XLA."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.client_step.ops import linreg_tier_step
    from repro.launch.plan import CacheSpec, ExecutionPlan

    print("phase B: Zipf linreg fleet (10k clients, dim 16, n_k 4..64), "
          "bucketed streaming plane, fused client step vs XLA")
    plan = ExecutionPlan(plane="streaming", chunk_rounds=CHUNK,
                         cache=CacheSpec(bucketed=True))
    runs = {}
    # the XLA arm's matmuls at float32 precision: the kernel's step is
    # exact fp32 VPU work, and the comparison is against that reference
    with jax.default_matmul_precision("float32"):
        for fused in (True, False):
            tr = _zipf_trainer(linreg_tier_step() if fused else None)
            runs[fused] = _run(tr, plan, clock,
                               "kernel" if fused else "xla   ")
            if fused:
                cache, sds = tr.stream_cache, tr.streaming_dataset()
                _, tiers, cids, ws, lrs, ixs, _ = tr._bucket_chunk(
                    ROUNDS - CHUNK, ROUNDS, cache.layout.tier_of,
                    sds.counts, sds.base_key())
                text = _compiled_text(
                    tr._bucketed_chunk_fn(CHUNK, tiers, False), tr.state,
                    cache.view(), sds.base_key(), jnp.int32(ROUNDS - CHUNK),
                    jnp.asarray(lrs), jax.tree.map(jnp.asarray, cids),
                    jax.tree.map(jnp.asarray, ws), ixs)
                _assert_kernel(text, "kernel")
    (lk, wk), (lx, wx) = runs[True], runs[False]
    print(f"  kernel vs xla: max |loss diff| {np.max(np.abs(lk - lx)):.3e}, "
          f"max |param diff| {np.max(np.abs(wk - wx)):.3e}")
    np.testing.assert_allclose(lk, lx, rtol=0, atol=ATOL_CLIENT_STEP,
                               err_msg="kernel vs xla losses")
    np.testing.assert_allclose(wk, wx, rtol=0, atol=ATOL_CLIENT_STEP,
                               err_msg="kernel vs xla final params")


def phase_mesh(clock):
    """Phase A's model on a 4-chip data mesh vs one chip."""
    from repro.core import fedmom
    from repro.launch.mesh import MeshSpec
    from repro.launch.plan import ExecutionPlan

    mesh_spec = MeshSpec(devices=4)
    print(f"mesh phase: LeNet / FEMNIST-shaped on a {mesh_spec.devices}-chip "
          f"data mesh vs one chip")
    failures = []
    for plane in ("device", "streaming"):
        runs = {}
        for arm, mesh, reverse in (("1 chip", None, False),
                                   ("1 chip, reversed cohort", None, True),
                                   ("mesh", mesh_spec, False)):
            tr = _lenet_trainer(fedmom(eta=500 / 64, beta=0.9,
                                       use_fused_kernel=True),
                                reverse_cohort=reverse)
            plan = ExecutionPlan(plane=plane, chunk_rounds=CHUNK, mesh=mesh)
            runs[arm] = _run(tr, plan, clock, f"{plane} {arm}")
            if mesh is not None:
                failures += _split_failures(tr, mesh_spec, plane)
        failures += _agreement_failures(
            runs["mesh"], runs["1 chip"], runs["1 chip, reversed cohort"],
            f"{plane} mesh vs 1 chip", 1, ATOL_MESH_HEAD)
    _raise_if(failures)


def _split_failures(tr, mesh_spec, plane):
    """The cohort really splits: the compiled sharded chunk reduces the
    per-device deltas with an all-reduce, and on the device plane the
    packed corpus is sharded over every mesh device.  A mesh that put
    everything on device 0 fails here."""
    import jax.numpy as jnp

    from repro.sharding import FED_MESH_RULES, axis_rules

    mesh = tr.session.mesh_for(mesh_spec)
    print(f"  mesh shape {dict(mesh.shape)}, devices "
          f"{[d.id for d in mesh.devices.flat]}")
    # the trainer's own context for a mesh run, re-entered to lower its
    # cached chunk program
    tr._mesh_spec = mesh_spec
    try:
        with axis_rules(mesh, FED_MESH_RULES):
            if plane == "device":
                data = tr.device_dataset()
                data_key = data.base_key()
            else:
                data = tr.stream_cache.view()
                data_key = tr.streaming_dataset().base_key()
            text = _compiled_text(
                tr._device_chunk_fn(CHUNK, False), tr.state, data,
                tr._sample_key(), data_key, jnp.int32(0),
                jnp.full((CHUNK,), tr.rcfg.lr, jnp.float32))
    finally:
        tr._mesh_spec = None
    failures = []
    arr = next(iter(data.arrays.values())) if plane == "device" else next(
        iter(data.tier_arrays[0].values()))
    shards = {s.device.id: s.data.shape for s in arr.addressable_shards}
    print(f"  {plane}: corpus placement (device id -> shard shape): {shards}")
    if plane == "device" and (len(shards) != mesh_spec.devices or any(
            s[0] * mesh_spec.devices != arr.shape[0]
            for s in shards.values())):
        failures.append("device: the packed corpus is not split over the "
                        "mesh")
    if "all-reduce" in text:
        print(f"  {plane}: the sharded chunk program reduces per-device "
              f"deltas (all-reduce)")
    else:
        failures.append(f"{plane}: no all-reduce in the sharded chunk "
                        f"program")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"chip_smoke (smoke, not a benchmark) on {dev.device_kind} x "
          f"{len(jax.devices())}; compile cache {enable_compile_cache()}")
    clock = _CompileClock()
    phases = [phase_mesh] if args.chips == 4 else [phase_a, phase_b]
    for phase in phases:
        t0, c0 = time.perf_counter(), clock.seconds
        phase(clock)
        print(f"  {phase.__name__}: {time.perf_counter() - t0:.3f} s wall, "
              f"{clock.seconds - c0:.3f} s compiling")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
