"""The per-layer split (``chipbench.scopes``): the tiny cells' compiled
chunk names the four layers; scope self time, ``unscoped`` and the gap
labels on synthetic lists; the device lists as ``trace.load`` reads them;
the metrics that exist today unchanged on the committed trace; the
recorded chip traces split into their layers."""
import json
import re
from pathlib import Path

import jax
import pytest

from chipbench import harness, registry, scopes, trace
from chipbench.peaks import peaks
from chipbench_tiny import tiny_cell

DATA = Path(__file__).parent / "data"
LAYERS = ("fed.gather", "fed.client", "fed.aggregate", "fed.server")
CELLS = ("lenet-femnist.device.c100", "charlstm-shakespeare.device.c100")


@pytest.mark.parametrize("name", CELLS)
def test_tiny_cell_chunk_names_the_four_layers(name, monkeypatch):
    from repro.launch.plan import TrainSession

    texts = []
    real = TrainSession.jit_fn

    def jit_fn(self, key, build):
        fn = real(self, key, build)

        def call(*args):
            texts.append(fn.lower(*args).compile().as_text())
            return fn(*args)

        return call

    monkeypatch.setattr(TrainSession, "jit_fn", jit_fn)
    cell, _ = tiny_cell(name)
    harness.checked_first_chunk(cell, 2**31 + 5, lambda msg: None)
    (text,) = texts                     # the device plane's one chunk
    found = {scopes.scope_of(n)
             for n in re.findall(r'op_name="([^"]*)"', text)}
    assert set(LAYERS) <= found


def test_scope_of_takes_the_innermost_fed_component():
    path = "jit(fn)/while/body/closed_call/fed.client/vmap()/while/dot:"
    assert scopes.scope_of(path) == "fed.client"
    assert scopes.scope_of("jit(f)/fed.gather/x/fed.client/y") == "fed.client"
    assert scopes.scope_of("jit(f)/transpose(jvp(fed.client))/mul") \
        == "fed.client"
    assert scopes.scope_of("jit(fn)/while") == scopes.UNSCOPED
    assert scopes.scope_of("jit(f)/shuffed.x/add") == scopes.UNSCOPED
    assert scopes.scope_of("") == scopes.UNSCOPED


def _synthetic():
    # ns; window [0, 2000).  A while op holds three body ops; fusion.2 runs
    # in two programs under two scopes.  Gaps: [1000, 1200) inside the
    # fed.drain span, [1500, 2000) inside bench.window alone.
    return {
        "devices": {0: [
            ("%while.1 = (...) while(...)", 0, 1000, "unscoped", 7),
            ("%fusion.2 = f32[] fusion(...)", 100, 300, "fed.gather", 7),
            ("%fusion.3 = f32[] fusion(...)", 400, 200, "fed.client", 7),
            ("%fusion.2 = f32[] fusion(...)", 700, 100, "fed.client", 8),
            ("%fusion.4 = f32[] fusion(...)", 1200, 300, "fed.server", 7),
        ]},
        "spans": [("bench.window", 0, 2000), ("fed.dispatch", 0, 50),
                  ("fed.drain", 1000, 150)],
    }


def test_scope_self_time_and_unscoped():
    red = scopes.reduce(_synthetic(), rounds=2)
    assert red.scope_s == pytest.approx({
        "unscoped": 400e-9, "fed.gather": 300e-9, "fed.client": 300e-9,
        "fed.server": 300e-9})
    # the same short name in two programs keeps each program's scope
    assert red.op_scope_s[("fed.gather", "fusion.2")] == pytest.approx(
        300e-9)
    assert red.op_scope_s[("fed.client", "fusion.2")] == pytest.approx(
        100e-9)
    assert sum(red.scope_s.values()) == pytest.approx(red.base.busy_s[0])
    assert red.ms_per_round()["fed.server"] == pytest.approx(150e-6)


def test_gaps_take_the_innermost_span_of_either_prefix():
    data = _synthetic()
    red = scopes.reduce(data, rounds=2)
    assert red.gaps == [("bench.window, after fusion.4",
                         pytest.approx(500e-9)),
                        ("fed.drain, after while.1", pytest.approx(200e-9))]
    # where no fed. span covers a gap, its label is trace.reduce's
    assert red.base.gaps[0] == red.gaps[0]
    assert red.base.gaps[1][0] == "bench.window, after while.1"


def test_base_is_trace_reduce_on_the_same_ops():
    data = _synthetic()
    red = scopes.reduce(data, rounds=2, window=(50, 1800))
    plain = trace.reduce(
        {"devices": {0: [e[:3] for e in data["devices"][0]]},
         "spans": [s for s in data["spans"] if s[0].startswith("bench.")]},
        window=(50, 1800))
    assert red.base == plain
    assert sum(red.scope_s.values()) == pytest.approx(
        sum(plain.op_s.values()))


def test_load_reads_device_ops_as_profile_data_does(tmp_path):
    """A written XSpace: the scoped reading's device lists are
    ``trace.load``'s (whole nanoseconds, as ProfileData rounds them), each
    op with its ``tf_op`` scope and program; host spans of both
    prefixes."""
    space = scopes._xspace_class()()

    def plane(name):
        p = space.planes.add(id=len(space.planes), name=name)
        for i, stat in enumerate(("tf_op", "program_id"), start=1):
            p.stat_metadata.add(key=i).value.name = stat
        return p

    dev = plane("/device:TPU:0")
    for key, (name, tf_op, program) in enumerate([
            ("%fusion.1 = f32[] fusion(...)", "jit(f)/fed.client/dot:", 5),
            ("%copy.2 = f32[] copy(...)", "", 5),
            ("%fusion.1 = f32[] fusion(...)", "jit(g)/fed.gather/add:", 6),
    ], start=1):
        m = dev.event_metadata.add(key=key).value
        m.id, m.name = key, name
        if tf_op:
            m.stats.add(metadata_id=1, str_value=tf_op)
        m.stats.add(metadata_id=2, uint64_value=program)
    ops = dev.lines.add(id=1, name="XLA Ops", timestamp_ns=1000)
    for key, off, dur in [(1, 1250, 2750), (2, 5000, 999), (3, 7999, 500)]:
        ops.events.add(metadata_id=key, offset_ps=off, duration_ps=dur)
    other = dev.lines.add(id=2, name="XLA Modules", timestamp_ns=1000)
    other.events.add(metadata_id=1, offset_ps=0, duration_ps=10000)
    host = plane("/host:CPU")
    for key, name in enumerate(("bench.window", "fed.drain", "other"), 1):
        m = host.event_metadata.add(key=key).value
        m.id, m.name = key, name
    py = host.lines.add(id=1, name="python", timestamp_ns=990)
    for key, off, dur in [(1, 0, 20000), (2, 3000, 4000), (3, 0, 1000)]:
        py.events.add(metadata_id=key, offset_ps=off, duration_ps=dur)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())

    got = scopes.load(str(path))
    want = trace.load(str(path))
    assert [e[:3] for e in got["devices"][0]] == want["devices"][0]
    assert [e[3:] for e in got["devices"][0]] == [
        ("fed.client", 5), ("unscoped", 5), ("fed.gather", 6)]
    assert got["devices"][0][0][1:3] == (1001.0, 2.0)
    assert sorted(n for n, _, _ in got["spans"]) == ["bench.window",
                                                    "fed.drain"]
    assert [s for s in got["spans"] if s[0] == "bench.window"] \
        == want["spans"]


def _committed_trace():
    rec = json.loads((DATA / "charlstm_trace_3ms.json").read_text())
    return rec, {"devices": {int(k): [tuple(e) for e in v]
                             for k, v in rec["devices"].items()},
                 "spans": [tuple(s) for s in rec["spans"]]}


def _context(cell, layers=None, reduction=None, events=()):
    """A traced run's context as ``run_cell`` builds it, around the
    committed trace's numbers."""
    if layers is not None:
        reduction = layers.base
    return harness.Context(
        cell=cell.name, config=cell.config, traffic=cell.traffic,
        model=cell.model, chips=1, peaks=peaks("TPU v5 lite"),
        setup_s=20.137, window_s=10.149, window_rounds=125,
        window_history=[], events=list(events), trace=reduction,
        layers=layers)


def _through_traced(monkeypatch, data, window, rounds):
    """``harness._traced`` on ``data`` in place of a profiler trace whose
    ``bench.window`` span is ``window``."""
    lo, hi = window
    spans = [s for s in data["spans"] if s[0] != trace.WINDOW_SPAN]
    spanned = dict(data, spans=spans + [(trace.WINDOW_SPAN, lo, hi - lo)])
    monkeypatch.setattr(harness, "_run", lambda tr, n, plan: [])
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(trace, "find_xplane", lambda d: "trace.xplane.pb")
    monkeypatch.setattr(scopes, "load", lambda path: spanned)
    return harness._traced(None, rounds, None, [0], lambda msg: None)


@pytest.mark.parametrize("name,value", [
    ("round_ms", 81.192), ("setup_s", 20.137), ("mfu", 2.435347835024631),
    ("device_idle_frac", 0.9352106666666666)])
def test_todays_metrics_read_the_same_on_the_committed_trace(
        name, value, monkeypatch):
    """The metrics of BENCHMARK.json read from the scoped reduction's base
    what they read from ``trace.reduce``, and what they read before the
    scopes existed; and so through ``harness._traced``, the traced run's
    path."""
    rec, data = _committed_trace()
    window = tuple(rec["window"])
    scoped = {"devices": {d: [e + (scopes.UNSCOPED, 0) for e in evs]
                          for d, evs in data["devices"].items()},
              "spans": data["spans"]}
    cell = harness.Cell.load(CELLS[1], registry.benchmark())
    contexts = [
        _context(cell, reduction=trace.reduce(data, window=window)),
        _context(cell, reduction=scopes.reduce(scoped, rounds=1,
                                               window=window).base),
        _context(cell, layers=_through_traced(monkeypatch, scoped, window,
                                              rounds=1))]
    readings = [registry.metric(name).read(ctx) for ctx in contexts]
    assert readings[0] == readings[1] == readings[2] == pytest.approx(
        value, rel=1e-12)


def _recorded(name):
    rec = json.loads((DATA / name).read_text())
    return rec, {"devices": {int(k): [tuple(e) + (0,) for e in v]
                             for k, v in rec["devices"].items()},
                 "spans": [tuple(s) for s in rec["spans"]]}


@pytest.mark.parametrize("name", ["lenet_scoped_trace_3ms.json",
                                  "charlstm_scoped_trace_1ms.json"])
def test_recorded_chip_trace_splits_into_layers(name):
    """A few ms of each cell's traced stretch on the chip, around a round's
    first gather: gather and client time both show, what no scope claims
    stays under a tenth of the busy time, and the scopes add up to it."""
    rec, data = _recorded(name)
    lo, hi = rec["window"]
    red = scopes.reduce(data, rounds=1, window=(lo, hi))
    busy = red.base.busy_s[0]
    assert red.scope_s["fed.gather"] > 0
    assert red.scope_s["fed.client"] > 0
    assert red.scope_s.get(scopes.UNSCOPED, 0.0) < 0.10 * busy
    assert sum(red.scope_s.values()) == pytest.approx(busy, rel=1e-6)


SCOPE_METRICS = {"gather_ms": "fed.gather", "client_ms": "fed.client",
                 "aggregate_ms": "fed.aggregate", "server_ms": "fed.server"}


@pytest.mark.parametrize("name", ["lenet_scoped_trace_3ms.json",
                                  "charlstm_scoped_trace_1ms.json"])
def test_scope_metrics_add_up_to_the_busy_time(name, monkeypatch):
    """The four scope metrics and ``unscoped`` add up to the busy ms per
    round, read through ``harness._traced`` as a traced run reads them."""
    rec, data = _recorded(name)
    rounds = 2
    layers = _through_traced(monkeypatch, data, tuple(rec["window"]),
                             rounds)
    ctx = _context(harness.Cell.load(CELLS[0], registry.benchmark()),
                   layers=layers)
    got = {m: registry.metric(m).read(ctx) for m in SCOPE_METRICS}
    assert got["gather_ms"] > 0 and got["client_ms"] > 0
    for metric, scope in SCOPE_METRICS.items():
        assert got[metric] == layers.scope_s.get(scope, 0.0) / rounds * 1e3
    unscoped = layers.ms_per_round().get(scopes.UNSCOPED, 0.0)
    busy_ms = layers.base.busy_s[0] / rounds * 1e3
    assert sum(got.values()) + unscoped == pytest.approx(busy_ms, rel=1e-5)


@pytest.mark.parametrize("metric", sorted(SCOPE_METRICS))
def test_scope_metric_reads_0_for_a_scope_with_no_ops(metric):
    data = _synthetic()
    data["devices"][0] = [e for e in data["devices"][0]
                          if e[3] != SCOPE_METRICS[metric]]
    layers = scopes.reduce(data, rounds=2)
    ctx = _context(harness.Cell.load(CELLS[0], registry.benchmark()),
                   layers=layers)
    assert registry.metric(metric).read(ctx) == 0.0


@pytest.mark.parametrize("metric", sorted(SCOPE_METRICS))
def test_scope_metric_is_none_untraced(metric):
    ctx = _context(harness.Cell.load(CELLS[0], registry.benchmark()))
    assert registry.metric(metric).read(ctx) is None


def test_pack_s_reads_the_first_chunks_pack_event():
    cell, _ = tiny_cell(CELLS[0])
    first = harness.checked_first_chunk(cell, 2**31 + 9, lambda msg: None)
    (pack,) = [e for e in first.events if e["event"] == "pack"]
    reader = registry.metric("pack_s")
    assert reader.read(_context(cell, events=first.events)) \
        == pack["seconds"] > 0
    assert reader.read(_context(cell, events=[{"event": "plan"}])) is None
