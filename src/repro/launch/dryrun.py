import os
# MUST run before any other import: jax locks the device count at first
# initialization.  Do NOT move below the jax import.  MERGES with a
# user-set XLA_FLAGS instead of clobbering it: an existing device-count
# force wins (the user asked for that many host devices), every other
# user flag is kept alongside ours.
_user_xla_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _user_xla_flags:
    os.environ["XLA_FLAGS"] = (
        _user_xla_flags + " --xla_force_host_platform_device_count=512"
    ).strip()

"""Multi-pod dry-run driver.

For every (architecture x input shape x mesh) combination this lowers and
compiles the real step function — the federated round (the paper's workload)
for train shapes, prefill / decode for serving shapes — against
ShapeDtypeStruct inputs on the production mesh, then extracts
memory / FLOPs / collective statistics for the roofline analysis.
Nothing is allocated; failures here are sharding bugs in the system.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b \
        --shape train_4k [--multi-pod] [--variant zero|replicated]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] --json out.json
"""
import argparse
import json
import sys
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.core import RoundConfig, round_step
from repro.core import server_opt as so
from repro.launch import hlo_analysis as ha
from repro.launch import hlo_cost
from repro.launch import hw
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    INPUT_SHAPES,
    placement_for,
    round_geometry,
    serve_batch_specs,
    shape_applicable,
    train_batch_specs,
)
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.sharding import (
    FED_MESH_RULES,
    FSDP_RULES,
    REPLICATED_SERVER_RULES,
    axis_rules,
    tree_shardings,
)

_IS_AXES = (lambda x: isinstance(x, tuple)
            and all(e is None or isinstance(e, str) for e in x))


# ---------------------------------------------------------------------------
# variants: named rule/config tweaks used for §Perf hillclimbing
# ---------------------------------------------------------------------------
# named rule overrides for §Perf hillclimbing (see EXPERIMENTS.md):
#   zero        - default: ZeRO-sharded server state (beyond-paper)
#   replicated  - paper-faithful replicated server state (baseline)
#   bf16delta   - aggregate the biased gradient in bf16 (halves all-reduce)
#   mp_serve    - serving: weights model-parallel only (no FSDP all-gather
#                 per token) for scan-placement archs
#   expert_dp   - serving MoE: experts sharded over the data axes
#                 (expert parallelism) + model-parallel FFN slices
#   seq_cache   - decode: shard the KV cache on the sequence axis (for
#                 batch=1 long-context decode, e.g. long_500k)
VARIANT_OVERRIDES = {
    "zero": {},
    "replicated": {"opt_embed": None},
    "bf16delta": {},
    # train: shard attention on head_dim when the head count does not divide
    # the model axis (qwen* 40-head class) — trades replicated-attn delta
    # all-reduce for per-layer weight gathers
    "headdim": {"head_dim": "model"},
    "headdim_bf16": {"head_dim": "model"},
    "mp_serve": {"embed": None},
    "expert_dp": {"embed": None, "expert": ("pod", "data")},
    "seq_cache": {"seq": ("pod", "data")},
    # decode: shard the KV cache along sequence over 'model' (GQA kv-head
    # counts < model extent leave the cache batch-sharded only otherwise)
    "seq_model": {"seq": "model"},
    # MoE: shard the per-expert FFN dim instead of the expert dim (avoids
    # the 8-experts-over-16-shards padding that doubles expert FLOPs)
    "moe_ffshard": {"expert": None, "expert_mlp": "model"},
    # rwkv: halve the chunk of the chunked scan (the intra-chunk decay
    # tensor traffic scales with S*C)
    "rwkv_chunk16": {},
    # moe: vmap group dispatch aligned with the data shards (kills the
    # token-contraction all-reduces of the sequential map in scan placement)
    "moe_vmap": {},
    # rg-lru: run the associative scan in bf16 (gates stay fp32)
    "rglru_bf16": {},
    # remat: save matmul outputs instead of full recompute
    "remat_dots": {},
    # rg-lru: bf16-gather u for the gate matmuls instead of fp32 psums
    "rglru_gather": {},
    # combined HC-2 step: vmap dispatch + bf16 delta aggregation
    "moe_vmap_bf16": {},
    # decode: 2D weight-stationary serving — weights sharded over data too,
    # partial-sum activations instead of weight gathers (batch<=dp decode)
    "w2d": {"embed": ("pod", "data")},
}


def rules_for(placement: str, variant: str, kind: str = "serve"):
    base = FSDP_RULES if placement == "scan" else FED_MESH_RULES
    rules = dict(base)
    if kind == "train" and placement == "mesh":
        # inside the client vmap the batch dim is per-client: the 'clients'
        # logical axis (spmd_axis_name) already consumes ('pod','data')
        rules["batch"] = None
    rules.update(VARIANT_OVERRIDES.get(variant, {}))
    return rules


def _f32_state_of(params_sds):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params_sds)


def _server_axes(axes):
    """ZeRO rule: the server master/momentum shards its 'embed'-like dims
    over the data axes via the 'opt_embed' logical axis."""
    return jax.tree.map(
        lambda t: tuple("opt_embed" if a == "embed" else a for a in t),
        axes, is_leaf=_IS_AXES)


# ---------------------------------------------------------------------------
# step builders: (jitted fn, example args, arg shardings)
# ---------------------------------------------------------------------------
def build_train(arch: str, cfg: ModelConfig, shape, mesh, variant: str,
                rules: dict):
    placement = placement_for(arch)
    C, H, b = round_geometry(shape, placement, mesh)

    params_sds, axes = T.abstract_params(cfg)
    state_sds = so.ServerState(
        w=_f32_state_of(params_sds),
        extra={"v": _f32_state_of(params_sds)},
        t=jax.ShapeDtypeStruct((), jnp.int32),
    )
    srv_axes = _server_axes(axes)
    w_sds_f32 = _f32_state_of(params_sds)
    state_sh = so.ServerState(
        w=tree_shardings(srv_axes, rules, mesh, w_sds_f32),
        extra={"v": tree_shardings(srv_axes, rules, mesh, w_sds_f32)},
        t=NamedSharding(mesh, P()),
    )
    b_sds, b_spec, w_sds, w_spec = train_batch_specs(
        arch, cfg, shape, placement, mesh)
    b_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), b_spec,
                        is_leaf=lambda x: isinstance(x, P))
    w_sh = NamedSharding(mesh, w_spec)

    delta_dtype = ("bfloat16"
                   if variant in ("bf16delta", "headdim_bf16",
                                  "moe_vmap_bf16")
                   else "float32")
    rcfg = RoundConfig(clients_per_round=C, local_steps=H, lr=0.01,
                       placement=placement, delta_dtype=delta_dtype,
                       compute_dtype=cfg.dtype)
    opt = so.fedmom(eta=1.0, beta=0.9)

    def loss_fn(p, batch):
        return T.loss_fn(p, cfg, batch)

    def step(state, batches, weights):
        return round_step(loss_fn, opt, state, batches, weights, rcfg,
                          param_axes=axes)

    fn = jax.jit(step, in_shardings=(state_sh, b_sh, w_sh))
    geo = dict(C=C, H=H, b=b,
               arg_bytes_per_dev=_arg_bytes_per_device(
                   (state_sds, b_sds, w_sds), (state_sh, b_sh, w_sh)))
    return fn, (state_sds, b_sds, w_sds), rules, geo


def build_serve(arch: str, cfg: ModelConfig, shape, mesh, variant: str,
                rules: dict):
    placement = placement_for(arch)
    params_sds, axes = T.abstract_params(cfg)
    params_sh = tree_shardings(axes, rules, mesh, params_sds)

    cache_len = shape.seq
    cache_sds, cache_axes = T.init_cache(cfg, shape.global_batch, cache_len,
                                         abstract=True)
    cache_sh = tree_shardings(cache_axes, rules, mesh, cache_sds)
    sds, spec = serve_batch_specs(arch, cfg, shape, mesh)
    b_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                        is_leaf=lambda x: isinstance(x, P))

    if shape.kind == "prefill":
        def step(params, batch, cache):
            return T.prefill(params, cfg, batch, cache)
        fn = jax.jit(step, in_shardings=(params_sh, b_sh, cache_sh))
        args = (params_sds, sds, cache_sds)
        shs = (params_sh, b_sh, cache_sh)
    else:
        pos_sh = b_sh.pop("pos")
        pos_sds = sds.pop("pos")
        def step(params, cache, tokens, pos):
            return T.decode_step(params, cfg, cache, tokens, pos)
        fn = jax.jit(step, in_shardings=(
            params_sh, cache_sh, b_sh["tokens"], pos_sh))
        args = (params_sds, cache_sds, sds["tokens"], pos_sds)
        shs = (params_sh, cache_sh, b_sh["tokens"], pos_sh)
    geo = {"arg_bytes_per_dev": _arg_bytes_per_device(args, shs)}
    return fn, args, rules, geo


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------
def _arg_bytes_per_device(args_sds, shardings) -> int:
    total = 0
    for s, sh in zip(jax.tree.leaves(args_sds), jax.tree.leaves(shardings)):
        shard_shape = sh.shard_shape(s.shape) if hasattr(sh, "shard_shape") \
            else s.shape
        n = 1
        for d in shard_shape:
            n *= d
        total += n * jnp.dtype(s.dtype).itemsize
    return total


def dry_run(arch: str, shape_name: str, *, multi_pod: bool = False,
            variant: str = "zero", verbose: bool = True) -> dict:
    cfg = get_config(arch)
    if variant == "rwkv_chunk16":
        cfg = cfg.replace(rwkv_chunk=16)
    elif variant == "moe_vmap":
        cfg = cfg.replace(moe_dispatch="vmap")
    elif variant == "rglru_bf16":
        cfg = cfg.replace(rglru_dtype="bfloat16")
    elif variant == "remat_dots":
        cfg = cfg.replace(remat_policy="dots")
    elif variant == "rglru_gather":
        cfg = cfg.replace(rglru_gate_gather=True)
    elif variant == "moe_vmap_bf16":
        cfg = cfg.replace(moe_dispatch="vmap")
    shape = INPUT_SHAPES[shape_name]
    ok, reason = shape_applicable(arch, cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "variant": variant, "placement": placement_for(arch),
        "kind": shape.kind,
    }
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    try:
        rules = rules_for(placement_for(arch), variant, shape.kind)
        with axis_rules(mesh, rules):
            if shape.kind == "train":
                fn, args, _, geo = build_train(arch, cfg, shape, mesh,
                                               variant, rules)
            else:
                fn, args, _, geo = build_serve(arch, cfg, shape, mesh,
                                               variant, rules)
            with mesh:
                lowered = fn.lower(*args)
                compiled = lowered.compile()
        rec.update(geo)
        rec["status"] = "ok"
        rec["lower_compile_s"] = round(time.time() - t0, 1)

        # ---- memory -----------------------------------------------------
        mem = None
        try:
            mem = compiled.memory_analysis()
        except Exception:
            pass
        if mem is not None:
            for field in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes"):
                v = getattr(mem, field, None)
                if v is not None:
                    rec[field] = int(v)
        # ---- cost (loop-aware; XLA's cost_analysis counts while bodies
        # once, verified empirically — see launch/hlo_cost.py) -------------
        hlo = compiled.as_text()
        la = hlo_cost.analyze(hlo)
        flops = la["flops"]
        bytes_accessed = la["bytes"]
        rec["hlo_flops_per_dev"] = flops
        rec["hlo_bytes_per_dev"] = bytes_accessed
        try:
            xc = compiled.cost_analysis() or {}
            if isinstance(xc, list):
                xc = xc[0] if xc else {}
            rec["xla_flops_raw"] = float(xc.get("flops", 0.0))
        except Exception:
            pass

        # ---- collectives (loop-aware) ------------------------------------
        rec["collectives"] = la["collectives"]
        rec["collective_bytes_per_dev"] = la["collective_bytes"]
        rec["collective_count"] = la["collective_count"]

        # ---- roofline ---------------------------------------------------
        terms = ha.roofline_terms(flops, bytes_accessed,
                                  la["collective_bytes"], hw.V5E)
        rec["roofline"] = terms

        tokens = shape.global_batch * (shape.seq if shape.kind != "decode"
                                       else 1)
        mf = ha.model_flops(cfg.n_active_params(), tokens,
                            backward=(shape.kind == "train"))
        rec["model_flops_total"] = mf
        hlo_total = flops * n_chips
        rec["model_flops_ratio"] = (mf / hlo_total) if hlo_total else None
    except Exception as e:  # noqa: BLE001 — report and continue the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=25)
    if verbose:
        _print_rec(rec)
    return rec


def _print_rec(rec: dict):
    if rec["status"] == "ok":
        r = rec.get("roofline", {})
        print(f"[OK]   {rec['arch']:22s} {rec['shape']:12s} {rec['mesh']:8s} "
              f"{rec['variant']:10s} compile={rec['lower_compile_s']:6.1f}s "
              f"flops/dev={rec['hlo_flops_per_dev']:.3e} "
              f"coll/dev={rec['collective_bytes_per_dev']:.3e}B "
              f"dominant={r.get('dominant', '?')}")
    elif rec["status"] == "skipped":
        print(f"[SKIP] {rec['arch']:22s} {rec['shape']:12s} {rec['mesh']:8s} "
              f"— {rec['reason'][:80]}")
    else:
        print(f"[ERR]  {rec['arch']:22s} {rec['shape']:12s} {rec['mesh']:8s} "
              f"{rec['error'][:160]}")
    sys.stdout.flush()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--variant", default="zero",
                    choices=list(VARIANT_OVERRIDES))
    ap.add_argument("--json", default=None, help="append records to file")
    args = ap.parse_args(argv)

    combos = []
    arches = list(ARCH_IDS) if args.all or not args.arch else [args.arch]
    shapes = list(INPUT_SHAPES) if args.all or not args.shape \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in arches:
        for s in shapes:
            for m in meshes:
                combos.append((a, s, m))

    records = []
    for a, s, m in combos:
        records.append(dry_run(a, s, multi_pod=m, variant=args.variant))
    if args.json:
        with open(args.json, "a") as f:
            for r in records:
                r.pop("traceback", None)
                f.write(json.dumps(r) + "\n")
    n_err = sum(r["status"] == "error" for r in records)
    print(f"\n{len(records)} combos: "
          f"{sum(r['status'] == 'ok' for r in records)} ok, "
          f"{sum(r['status'] == 'skipped' for r in records)} skipped, "
          f"{n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
