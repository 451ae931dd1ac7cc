"""The correctness check, run end to end on the CPU at a tiny size with the
chip look skipped: a sound run is correct; the timed path broken
underneath -- a server step that returns its state unchanged, half of each
minibatch left out, on a four-device mesh the exchange between devices
left out -- reads correct false.  Each cell's faults are planted in the
program."""
import time

import jax

from chipbench import harness
from chipbench_tiny import tiny_cell

CELL = "lenet-femnist.device.c100"
LSTM = "charlstm-shakespeare.device.c100"


def _run(cell, bench):
    result, lines = harness.run_cell(cell, bench, seed=2**31 + 11,
                                     seconds=0.1, trace=False,
                                     t_start=time.perf_counter(),
                                     require_chip=False, log=lambda m: None)
    assert set(result["check"]) == set(cell.limits["limits"])
    assert list(result)[-1] == "check"
    assert lines[0].startswith("check ")
    return result


def test_sound_run_is_correct():
    cell, bench = tiny_cell(CELL)
    result = _run(cell, bench)
    assert result["correct"], result["check"]
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert set(result["metrics"]) == {"round_ms", "setup_s"}


def _freeze(monkeypatch):
    """The server step returns its state unchanged."""
    from repro.core import server_opt

    monkeypatch.setattr(server_opt.ServerOpt, "update",
                        lambda self, state, delta: state._replace(
                            t=state.t + 1))


def _halve(monkeypatch):
    """Each local step takes the mean over half of its minibatch."""
    from repro.core import client

    real = client.local_update

    def half(loss_fn, params, batches, *a, **kw):
        batches = {k: v[:, : v.shape[1] // 2] for k, v in batches.items()}
        return real(loss_fn, params, batches, *a, **kw)

    monkeypatch.setattr(client, "local_update", half)


def test_state_left_unchanged_fails(monkeypatch):
    _freeze(monkeypatch)
    cell, bench = tiny_cell(CELL)
    assert not _run(cell, bench)["correct"]


def test_half_batch_fails(monkeypatch):
    _halve(monkeypatch)
    cell, bench = tiny_cell(CELL)
    assert not _run(cell, bench)["correct"]


def test_lstm_sound_run_is_correct():
    result = _run(*tiny_cell(LSTM))
    assert result["correct"], result["check"]


def test_lstm_state_left_unchanged_fails(monkeypatch):
    _freeze(monkeypatch)
    assert not _run(*tiny_cell(LSTM))["correct"]


def test_lstm_half_batch_fails(monkeypatch):
    _halve(monkeypatch)
    assert not _run(*tiny_cell(LSTM))["correct"]


def _mesh_cell():
    """The LeNet cell on a data mesh of the four CPU devices."""
    cell, bench = tiny_cell(CELL)
    cell.chips = 4
    cell.traffic["plan"]["mesh_devices"] = 4
    cell.traffic["cohort"]["clients_per_round"] = 16
    return cell, bench


def test_mesh_sound_run_is_correct():
    result = _run(*_mesh_cell())
    assert result["correct"], result["check"]


def test_mesh_exchange_left_out_fails(monkeypatch):
    real_shard_map = jax.shard_map

    def unchecked(*a, **kw):
        # each chip keeps its own partial delta: no psum, and no type
        # check that would refuse the unreduced value as replicated
        return real_shard_map(*a, **dict(kw, check_vma=False))

    monkeypatch.setattr(jax, "shard_map", unchecked)
    monkeypatch.setattr(jax.lax, "psum", lambda x, axes: x)
    assert not _run(*_mesh_cell())["correct"]
