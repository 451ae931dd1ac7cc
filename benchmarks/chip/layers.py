"""Split one cell's federated round by the program's layers, from a device
trace of the cell at its full size:

    python3 benchmarks/chip/layers.py --workload <cell> --seed <n> \
        [--fixture out.json]

In one process, on the chip like ``run.py``: the cell's set-up and first
chunk (``harness.checked_first_chunk``), a warm chunk, then three stretches
of the same rounds on the same cohorts -- without the profiler, under it,
without it again -- so that the tracing's cost reads off the host clock.
The traced stretch is reduced by ``chipbench.scopes``; the line printed last
on standard output is one JSON object:

* ``layers_ms``: self ms per round of each ``fed.*`` named scope and of
  ``unscoped``, and ``busy_ms`` (the device's busy time per round) they add
  up to;
* ``gather_ms``, ``client_ms``, ``aggregate_ms``, ``server_ms``: the four
  scopes alone; ``pack_s``: the program's ``pack`` event (None when no
  corpus was packed);
* ``round_ms``: host clock per round of each stretch, and the traced one's
  excess over the mean of the other two;
* ``ops``: the heaviest ops of each scope (ms per round), and every
  unscoped op over 1 % of the busy time;
* ``gaps``: the longest idle stretches, labelled by the innermost host span
  (``bench.`` or ``fed.``), and ``spans``: count and total ms of each host
  span;
* ``same_as_trace``: whether ``trace.reduce`` on ``trace.load``'s lists
  gives the same busy time, op seconds and gaps as the scoped reading.

``--fixture`` writes a few ms of the traced stretch around the start of a
round's gather, ops with their scope, for the tests.  Needs the chip;
not run by the benchmark's own runs.
"""
import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
SCOPES = ("fed.gather", "fed.client", "fed.aggregate", "fed.server")


def _timed(harness, tr, n, plan):
    t = time.perf_counter()
    harness._run(tr, n, plan)
    return (time.perf_counter() - t) / n * 1e3


def _fixture(data, lo, hi, ms, device):
    """``ms`` of the traced stretch centred on the start of a round's
    gather -- the first ``fed.gather`` op in the stretch's second half that
    follows another layer's op -- ops with their scope, as ``tests/data``
    keeps them."""
    from chipbench import scopes

    evs = data["devices"][device]
    mid = lo + (hi - lo) / 2
    at, last = mid, None
    for _, s, _, scope, _ in sorted(evs, key=lambda e: (e[1], -e[2])):
        if scope == scopes.UNSCOPED:
            continue
        if (s >= mid and scope == "fed.gather" and last is not None
                and last != "fed.gather"):
            at = s
            break
        last = scope
    a, b = at - ms * 5e5, at + ms * 5e5
    return {"window": [a, b],
            "devices": {str(device): [[n[:120], s, d, scope]
                                      for n, s, d, scope, _ in evs
                                      if s < b and s + d > a]},
            "spans": [list(x) for x in data["spans"]
                      if x[1] < b and x[1] + x[2] > a]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fixture", help="write a few ms of the trace here")
    ap.add_argument("--fixture-ms", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
    import jax

    from chipbench import harness, registry, scopes
    from chipbench import trace as trace_lib

    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    harness.configure_jax()
    cell = harness.Cell.load(args.workload, registry.benchmark())
    used = harness.check_devices(cell.chips)[:cell.chips]
    first = harness.checked_first_chunk(cell, args.seed, log)
    tr, plan, seeds = first.trainer, first.plan, first.seeds
    packs = [r for r in first.events if r["event"] == "pack"]
    R = plan.chunk_rounds
    per_round = _timed(harness, tr, R, plan) / 1e3
    n = R * max(1, math.ceil(harness.TRACE_SECONDS / (per_round * R)))
    tr.sampler.seed = seeds.trace
    before = _timed(harness, tr, n, plan)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-layers-")
    try:
        jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
                traced = _timed(harness, tr, n, plan)
        finally:
            jax.profiler.stop_trace()
        after = _timed(harness, tr, n, plan)
        path = trace_lib.find_xplane(trace_dir)
        data = scopes.load(path)
        ids = [d.id for d in used]
        red = scopes.reduce(data, rounds=n, devices=ids)
        plain = trace_lib.reduce(trace_lib.load(path), devices=ids)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    base = red.base
    per = lambda s: s / n * 1e3  # noqa: E731
    busy = base.mean_busy_s
    layers = dict(sorted(red.ms_per_round().items()))
    ops = {}
    for scope in sorted(red.scope_s):
        top = sorted(((v, op) for (sc, op), v in red.op_scope_s.items()
                      if sc == scope), reverse=True)
        if scope == scopes.UNSCOPED:
            top = [x for x in top if x[0] > 0.01 * busy]
        else:
            top = top[:6]
        ops[scope] = [[op, per(v)] for v, op in top]
    spans = {}
    for name, _, d in data["spans"]:
        c, t = spans.get(name, (0, 0.0))
        spans[name] = (c + 1, t + d * 1e-6)
    dev = used[0]
    mean_untraced = (before + after) / 2
    out = {
        "workload": cell.name, "seed": args.seed, "rounds": n,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(used)},
        **{f"{s[len(scopes.SCOPE_PREFIX):]}_ms": layers.get(s)
           for s in SCOPES},
        "pack_s": packs[0]["seconds"] if packs else None,
        "pack": packs[0] if packs else None,
        "layers_ms": layers, "busy_ms": per(busy),
        "window_ms": per(base.window_s),
        "idle_frac": 1.0 - busy / base.window_s,
        "unscoped_share": red.scope_s.get(scopes.UNSCOPED, 0.0) / busy,
        "sum_over_busy": sum(red.scope_s.values()) / busy,
        "round_ms": {"untraced_before": before, "traced": traced,
                     "untraced_after": after,
                     "traced_excess": traced / mean_untraced - 1.0},
        "ops": ops,
        "gaps": [[label, s * 1e3] for label, s in red.gaps],
        "spans": {k: {"count": c, "ms": t} for k, (c, t) in
                  sorted(spans.items())},
        "same_as_trace": (plain.busy_s == base.busy_s
                          and plain.op_s == base.op_s
                          and plain.gaps == base.gaps),
        "seconds": time.perf_counter() - _T_START,
    }
    if args.fixture:
        lo, hi = scopes._window(data["spans"])
        rec = _fixture(data, lo, hi, args.fixture_ms, ids[0])
        rec = {"source": f"{dev.device_kind}, {cell.name}, "
                         f"{args.fixture_ms:g} ms of a traced stretch "
                         f"around a round's first fed.gather op; op "
                         f"names cut to 120 characters", **rec}
        Path(args.fixture).parent.mkdir(parents=True, exist_ok=True)
        Path(args.fixture).write_text(json.dumps(rec) + "\n")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
