"""Public wrapper for the fused FedMom server update.

On a TPU the Pallas kernel runs compiled; on the CPU (the tests) it runs in
interpret mode, which executes the same kernel body in Python — the tests
sweep shapes/dtypes against ref.py.

Mode selection goes through ``kernels._device.resolve_interpret``: the
committed device of concrete operands decides; inside jit the platform
named by ``kernel_platform`` (entered by ``FederatedTrainer.run``) does.
It never interprets in a TPU process.
"""
from __future__ import annotations

from repro.kernels._device import resolve_interpret, whole_on_each_device
from repro.kernels.fedmom_update import kernel as _k
from repro.kernels.fedmom_update import ref as _ref


def _fused(kind, w, s, delta, eta, beta):
    interpret = resolve_interpret((w, s, delta))
    return whole_on_each_device(
        lambda w, s, d: _k.fused_update_tree(
            w, s, d, eta=eta, beta=beta, kind=kind,
            interpret=interpret))(w, s, delta)


def fused_update_tree(w, v, delta, *, eta: float, beta: float,
                      use_kernel: bool = True):
    """FedMom (Nesterov): one fused launch over the whole parameter tree."""
    if not use_kernel:
        return _ref.fedmom_update(w, v, delta, eta, beta)
    return _fused("fedmom", w, v, delta, eta, beta)


def fused_avgm_tree(w, m, delta, *, eta: float, beta: float,
                    use_kernel: bool = True):
    """FedAvgM (heavy-ball): same fused stream, different update body."""
    if not use_kernel:
        return _ref.fedavgm_update(w, m, delta, eta, beta)
    return _fused("fedavgm", w, m, delta, eta, beta)
