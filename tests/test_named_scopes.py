"""The round's layers are named where the work happens: every plane's
compiled program carries the ``fed.*`` named scopes in its ops' ``op_name``
metadata, the chunk loop writes ``fed.*`` host spans with a ``chunk`` stat
onto the profiler's clock, and packing the device corpus appends one
``pack`` event to the history."""
import glob
import re

import jax
import numpy as np
import pytest

from _trajectory import (default_rcfg, make_clients, make_trainer,
                         strip_events)
from repro.core import fedmom
from repro.core.round import AGGREGATE, CLIENT, GATHER, SERVER
from repro.core.secure_agg import SecureAggSpec
from repro.kernels.client_step.ops import linreg_tier_step
from repro.launch.mesh import MeshSpec
from repro.launch.plan import CacheSpec, ExecutionPlan, TrainSession

SCOPES = {GATHER, CLIENT, AGGREGATE, SERVER}
BUCKETED = CacheSpec(bucketed=True)
# plane -> (trainer kwargs, plan kwargs, rcfg kwargs, scopes it must carry)
PLANES = {
    "per_round": ({}, {"plane": "per_round"}, {},
                  {CLIENT, AGGREGATE, SERVER}),
    "scanned": ({}, {"plane": "scanned"}, {}, {CLIENT, AGGREGATE, SERVER}),
    "device": ({}, {"plane": "device"}, {}, SCOPES),
    "device-scan-placement": ({}, {"plane": "device"},
                              {"placement": "scan"}, SCOPES),
    "device-secure": ({}, {"plane": "device",
                           "secure": SecureAggSpec(masked=True, seed=5)},
                      {}, SCOPES),
    "streaming": ({}, {"plane": "streaming"}, {}, SCOPES),
    "streaming-bucketed": ({}, {"plane": "streaming", "cache": BUCKETED},
                           {}, SCOPES),
    # the fused hook gathers inside the client step: no gather of its own
    "streaming-bucketed-hook": (
        {"client_step_fn": linreg_tier_step(use_kernel=True)},
        {"plane": "streaming", "cache": BUCKETED}, {},
        {CLIENT, AGGREGATE, SERVER}),
    "device-mesh4": ({}, {"plane": "device", "mesh": MeshSpec(devices=4)},
                     {"clients_per_round": 4}, SCOPES),
}


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def _scopes(text):
    return {s for name in _op_names(text)
            for s in re.findall(r"(?<![\w.])fed\.\w+", name)}


def record_compiled(monkeypatch):
    """Every program a trainer runs, compiled to text at its first call
    (inside the run's mesh and kernel contexts)."""
    texts = []
    real = TrainSession.jit_fn

    def jit_fn(self, key, build):
        fn = real(self, key, build)

        def call(*args):
            texts.append(fn.lower(*args).compile().as_text())
            return fn(*args)

        return call

    monkeypatch.setattr(TrainSession, "jit_fn", jit_fn)
    return texts


@pytest.mark.parametrize("plane", sorted(PLANES))
def test_every_plane_names_the_round_layers(plane, monkeypatch):
    trainer_kw, plan_kw, rcfg_kw, want = PLANES[plane]
    texts = record_compiled(monkeypatch)
    clients = make_clients(seed=3, n=8, lo=4, hi=40)
    tr = make_trainer(fedmom(), default_rcfg(**rcfg_kw), clients,
                      **trainer_kw)
    tr.run(4, plan=ExecutionPlan(chunk_rounds=2, **plan_kw), verbose=False)
    assert texts
    for text in texts:
        found = _scopes(text)
        assert want <= found, (plane, found)
        assert found <= SCOPES
        if GATHER not in want:
            assert GATHER not in found
    if plane == "device-mesh4":
        # the shard_map round's cross-device sum (its psum) is the
        # aggregation's; the gather's own all-reduces stay the gather's
        psums = [_op_names(line)[0] for text in texts
                 for line in text.splitlines()
                 if " all-reduce(" in line and "psum" in line]
        assert psums
        assert all(f"shard_map/{AGGREGATE}/" in name for name in psums)


def _spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return [(e.name, dict(e.stats)) for plane in
            ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("fed.")]


@pytest.mark.parametrize("plane,names", [
    ("device", {"fed.dispatch", "fed.seal", "fed.drain"}),
    ("streaming", {"fed.stage", "fed.upload", "fed.dispatch", "fed.seal",
                   "fed.drain"}),
])
def test_chunk_loop_spans_carry_their_chunk(plane, names, tmp_path):
    clients = make_clients(seed=5, n=8)
    tr = make_trainer(fedmom(), default_rcfg(), clients)
    plan = ExecutionPlan(plane=plane, chunk_rounds=2)
    tr.run(6, plan=plan, verbose=False)          # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.run(6, plan=plan, verbose=False)
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    assert {n for n, _ in spans} == names
    for name in names:
        # one span per chunk, named by the chunk's first round
        assert sorted(st["chunk"] for n, st in spans if n == name) \
            == [0, 2, 4], name


def test_pack_appends_one_event_per_pack():
    clients = make_clients(seed=7, n=8)
    tr = make_trainer(fedmom(), default_rcfg(), clients)
    plan = ExecutionPlan(plane="device", chunk_rounds=2)
    tr.run(4, plan=plan, verbose=False)
    (pack,) = [r for r in tr.history if r.get("event") == "pack"]
    dds = tr.device_dataset()
    assert pack["bytes"] == dds.nbytes > 0
    assert pack["clients"] == len(clients)
    assert pack["seconds"] > 0 and "round" not in pack
    n = len(tr.history)
    tr.run(4, plan=plan, verbose=False)        # the session's corpus again
    assert [r for r in tr.history[n:] if "event" in r] == []
    assert [r["round"] for r in strip_events(tr.history)] \
        == list(range(4)) * 2


def test_planes_without_a_packed_corpus_append_no_pack():
    clients = make_clients(seed=7, n=8)
    for plane in ("per_round", "scanned", "streaming"):
        tr = make_trainer(fedmom(), default_rcfg(), clients)
        tr.run(2, plan=ExecutionPlan(plane=plane, chunk_rounds=2),
               verbose=False)
        assert strip_events(tr.history) == tr.history, plane
    assert np.isfinite(tr.history[-1]["loss"])
