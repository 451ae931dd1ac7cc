"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,...,12 --control-seeds 1,2,3 [--out file.jsonl]

For every seed, in one process: the cell's set-up and first chunk through
``FederatedTrainer.run`` (``harness.checked_first_chunk``, the timed path
at the cell's size), then the reference; the gaps between them are the
program's readings (the lower end of a limit).  For the control seeds also
the gaps of, in the program's place, the reference in bfloat16 (the
control) and the reference with each planted fault (``reference.FAULTS``):
the upper end.  Not run by the benchmark's own runs.  Needs the chip, like
``run.py``.
"""
import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
    import jax.numpy as jnp

    from chipbench import harness, reference, registry
    from chipbench.population import Seeds

    harness.configure_jax()
    cell = harness.Cell.load(args.workload, registry.benchmark())
    harness.check_devices(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    rounds = cell.traffic["plan"]["chunk_rounds"]
    loss_rounds = cell.limits["loss_rounds"]
    out = open(args.out, "a") if args.out else None
    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    for seed in sorted(set(seeds) | control):
        rec = {"workload": cell.name, "seed": seed}
        t0 = time.perf_counter()
        if seed in seeds:
            first = harness.checked_first_chunk(cell, seed, log)
            clients, counts, s = first.clients, first.counts, first.seeds
            program = first.program
            first.trainer = None
            gc.collect()
            rec["program_s"] = time.perf_counter() - t0
        else:
            s = Seeds.from_seed(seed)
            clients, counts = cell.model.make_data(cell.config, s.data)
        t1 = time.perf_counter()
        ref = reference.follow(cell, clients, counts, s, rounds=rounds)
        rec["reference_s"] = time.perf_counter() - t1
        if seed in seeds:
            rec["program_gaps"] = reference.gaps(program, ref, loss_rounds)
        if seed in control:
            arms = [("control", dict(dtype=jnp.bfloat16))] + [
                (f, dict(fault=f, shards=cell.chips))
                for f in reference.FAULTS
                if f != "no_exchange" or cell.chips > 1]
            for arm, kw in arms:
                got = reference.follow(cell, clients, counts, s,
                                       rounds=rounds, **kw)
                rec[f"{arm}_gaps"] = reference.gaps(got, ref, loss_rounds)
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        del clients
        gc.collect()
    if out:
        out.close()
    print(f"calibrate: {time.perf_counter() - _T_START:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
