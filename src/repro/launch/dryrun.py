import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))
# The two lines above MUST run before any other import: jax locks the device
# count at first init. Do not set this flag globally (tests see 1 device).

"""Multi-pod dry-run: lower + compile EVERY (architecture x input shape)
cell on the production meshes and record memory/cost/collective analysis.

  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch whisper-medium \
      --shape decode_32k --mesh multi_pod --remap-tier 1   # cross-KV remap

Artifacts land in artifacts/dryrun/<mesh>/<arch>__<shape>[__remapX].json and
feed benchmarks/roofline.py (EXPERIMENTS.md §Dry-run / §Roofline).
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (
    ARCHS, SHAPES, SHAPES_BY_NAME, get_arch, shape_applicable,
)
from repro.distributed.analytic_cost import cost_for
from repro.distributed.hlo_analysis import collective_bytes
from repro.distributed.sharding import (
    DEFAULT_RULES, mesh_context, num_data_shards, sharding_for,
)
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import opt_shardings
from repro.models import build_model
from repro.training import OptimizerConfig, make_optimizer, make_train_step

ARTIFACT_DIR = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")


def pick_optimizer(cfg) -> str:
    return "adafactor" if cfg.param_count() > 15e9 else "adamw"


def auto_microbatches(cfg, shape, mesh, carry_budget: float = 4 * 2**30) -> int:
    """Smallest power-of-two microbatch count keeping the remat scan carry
    (activations at layer boundaries) under ``carry_budget`` per device."""
    shards = num_data_shards(mesh)
    tokens_dev = shape.global_batch * shape.seq_len / max(shards, 1)
    layers = cfg.num_layers + cfg.num_encoder_layers
    carry = tokens_dev * cfg.d_model * 2 * layers
    mb = 1
    while carry / mb > carry_budget and mb < shape.global_batch // shards:
        mb *= 2
    return mb


# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------

def lower_cell(arch: str, shape_name: str, mesh, *,
               remap_tier: float = 0.0, microbatches: int = 0,
               remat_policy: str = "full", profile: str = "train"):
    from repro.distributed.sharding import SERVING_RULES, ShardingRules
    cfg = get_arch(arch)
    shape = SHAPES_BY_NAME[shape_name]
    model = build_model(cfg)
    if profile == "serving":
        rules = SERVING_RULES
    elif profile == "train-ef":
        # §Perf variant: FSDP the per-expert d_ff dim instead of d_model
        rules = ShardingRules.make(
            expert_ff=("pod", "data"), expert_mlp=())
    elif profile == "head-tp":
        # §Perf variant (xlstm): 4 heads < model axis defeats head TP;
        # shard the 512-wide head_dim over model instead (contractions
        # over d_k become psums)
        rules = ShardingRules.make(
            profile="serving", head_dim=("model",), heads=())
    else:
        rules = DEFAULT_RULES
    if microbatches == 0 and shape.kind == "train":
        microbatches = auto_microbatches(cfg, shape, mesh)

    with mesh_context(mesh, rules):
        params_abs = model.abstract_params(mesh, rules)
        batch_abs = model.abstract_inputs(shape, mesh, rules)

        if shape.kind == "train":
            opt_name = pick_optimizer(cfg)
            opt = make_optimizer(OptimizerConfig(name=opt_name))
            step_fn = make_train_step(
                model, opt, remat_policy=remat_policy,
                microbatches=microbatches)
            opt_abs = jax.eval_shape(opt.init, params_abs)
            p_sh = model.param_shardings(mesh, rules)
            o_sh = opt_shardings(opt_abs, p_sh, mesh)
            opt_abs = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                opt_abs, o_sh)
            lowered = jax.jit(step_fn, donate_argnums=(0, 1)).lower(
                params_abs, opt_abs, batch_abs)
            return lowered, model, shape

        if shape.kind == "prefill":
            def prefill_fn(params, batch):
                return model.prefill(params, batch, shape.seq_len)
            lowered = jax.jit(prefill_fn).lower(params_abs, batch_abs)
            return lowered, model, shape

        # decode
        state_abs = model.abstract_decode_state(
            shape.global_batch, shape.seq_len, mesh, rules)
        tokens_abs = jax.ShapeDtypeStruct(
            (shape.global_batch,), jnp.int32,
            sharding=sharding_for(("batch",), (shape.global_batch,), mesh, rules))
        if remap_tier <= 0.0:
            def decode_fn(params, state, tokens):
                return model.decode_step(params, state, tokens, shape.seq_len)
            lowered = jax.jit(decode_fn, donate_argnums=(1,)).lower(
                params_abs, state_abs, tokens_abs)
            return lowered, model, shape
        if not cfg.is_encoder_decoder:
            # the layer remap step is the serving engine's own
            # (serving.engine.make_decode), compiled for the chip by
            # tests/test_tpu_compile.py
            raise ValueError("--remap-tier lowers the enc-dec cross-KV "
                             "remap only")
        # beyond-paper: enc-dec models remap the immutable CROSS-KV the
        # same way as parameters (it never changes after prefill)
        return _lower_cross_kv_remap(
            model, shape, mesh, rules, params_abs, state_abs, tokens_abs
        ), model, shape


def _lower_cross_kv_remap(model, shape, mesh, rules, params_abs, state_abs,
                          tokens_abs):
    """Whisper-family: hold the (immutable) cross-attention KV in
    pinned_host — the parameters' remapping argument applies verbatim to any
    inference-immutable state. The layer scan slices one repeat's cross KV
    per iteration; XLA's memory-space propagation inserts the host->device
    copy for each slice, overlapped like the parameter streams."""
    def to_host(a):
        host = jax.sharding.NamedSharding(
            mesh, a.sharding.spec, memory_kind="pinned_host")
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=host)

    blocks = state_abs["blocks"][0]
    state_host_cross = {
        **state_abs,
        "blocks": ({"mixer": {
            "self": blocks["mixer"]["self"],
            "cross": jax.tree.map(to_host, blocks["mixer"]["cross"]),
        }},),
    }

    dev_sh = jax.tree.map(
        lambda a: jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(*a.sharding.spec[1:])
            if len(a.sharding.spec) else jax.sharding.PartitionSpec(),
            memory_kind="device"),
        blocks["mixer"]["cross"])

    def decode_fn(params, state, tokens):
        def cross_transform(cross_slice):
            return jax.tree.map(jax.device_put, cross_slice, dev_sh)
        return model.impl.decode_step(
            params, state, tokens, shape.seq_len,
            cross_transform=cross_transform)

    lowered = jax.jit(decode_fn).lower(
        params_abs, state_host_cross, tokens_abs)
    cross_bytes = sum(
        int(np.prod(a.sharding.shard_shape(a.shape))) * a.dtype.itemsize
        for a in jax.tree.leaves(blocks["mixer"]["cross"]))
    lowered._mirage_extras = {
        "cross_kv_host_bytes_per_device": cross_bytes,
        "alpha": 0, "m": 0,
        "cycle_bytes_per_device": cross_bytes,
        "resident_bytes_per_device": 0,
    }
    return lowered


# ---------------------------------------------------------------------------
# analysis + artifact
# ---------------------------------------------------------------------------

def analyze(lowered, model, shape, mesh, *, hlo_text: bool = True) -> Dict[str, Any]:
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    rec: Dict[str, Any] = {
        "compile_s": round(compile_s, 2),
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "code_bytes": int(ma.generated_code_size_in_bytes),
            "host_argument_bytes": int(ma.host_argument_size_in_bytes),
            "host_temp_bytes": int(ma.host_temp_size_in_bytes),
        },
        "cost_analysis_raw": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
        },
    }
    if hlo_text:
        txt = compiled.as_text()
        stats = collective_bytes(txt)
        rec["collectives"] = {
            "bytes_by_op": stats.bytes_by_op,
            "count_by_op": stats.count_by_op,
            "total_bytes": stats.total_bytes,
        }
    if hasattr(lowered, "_mirage_extras"):
        rec["mirage"] = lowered._mirage_extras
    n_dev = mesh.size
    cost = cost_for(model.cfg, shape, num_data_shards(mesh))
    rec["analytic"] = {
        "flops_by_component": cost.flops,
        "hbm_bytes_by_component": cost.hbm_bytes,
        "total_flops": cost.total_flops,
        "total_hbm_bytes": cost.total_bytes,
        "model_flops": cost.model_flops,
        "useful_fraction": cost.useful_fraction,
    }
    rec["mesh"] = {"shape": dict(mesh.shape), "devices": n_dev}
    return rec


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             remap_tier: float = 0.0, force: bool = False,
             microbatches: int = 0, remat_policy: str = "full",
             profile: str = "train",
             out_dir: Optional[str] = None) -> Dict[str, Any]:
    out_dir = out_dir or os.path.abspath(ARTIFACT_DIR)
    tag = f"{arch}__{shape_name}" + (
        f"__remap{remap_tier:g}" if remap_tier else "")
    if microbatches != 0:
        tag += f"__mb{microbatches}"
    if remat_policy != "full":
        tag += f"__remat-{remat_policy}"
    if profile != "train":
        tag += f"__{profile}"
    path = os.path.join(out_dir, mesh_name, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi_pod"))
    t0 = time.time()
    lowered, model, shape = lower_cell(
        arch, shape_name, mesh, remap_tier=remap_tier,
        microbatches=microbatches, remat_policy=remat_policy,
        profile=profile)
    lower_s = time.time() - t0
    rec = analyze(lowered, model, shape, mesh)
    rec.update({
        "arch": arch, "shape": shape_name, "mesh_name": mesh_name,
        "remap_tier": remap_tier, "lower_s": round(lower_s, 2),
        "microbatches": microbatches, "remat_policy": remat_policy,
        "profile": profile,
    })
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default=None, choices=[None, "single_pod", "multi_pod"])
    ap.add_argument("--remap-tier", type=float, default=0.0,
                    help="> 0: enc-dec cross-KV held in pinned_host")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="0 = auto (carry-budget heuristic)")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--profile", default="train",
                    choices=["train", "serving", "train-ef", "head-tp"])
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    meshes = [args.mesh] if args.mesh else ["single_pod", "multi_pod"]
    ok, fail = 0, 0
    for mesh_name in meshes:
        for arch in archs:
            cfg = get_arch(arch)
            shapes = [args.shape] if args.shape else [s.name for s in SHAPES]
            for shape_name in shapes:
                runs, why = shape_applicable(cfg, SHAPES_BY_NAME[shape_name])
                if not runs:
                    print(f"SKIP  {mesh_name:10s} {arch:24s} {shape_name}: {why}")
                    continue
                try:
                    rec = run_cell(
                        arch, shape_name, mesh_name,
                        remap_tier=args.remap_tier, force=args.force,
                        microbatches=args.microbatches,
                        remat_policy=args.remat, profile=args.profile)
                    m = rec["memory"]
                    per_dev = (m["argument_bytes"] + m["temp_bytes"]
                               - m["alias_bytes"])
                    print(f"OK    {mesh_name:10s} {arch:24s} {shape_name:12s} "
                          f"lower {rec['lower_s']:6.1f}s compile "
                          f"{rec['compile_s']:6.1f}s "
                          f"perdev {per_dev/2**30:7.2f} GiB "
                          f"coll {rec['collectives']['total_bytes']/2**20:9.1f} MiB")
                    ok += 1
                except Exception as e:
                    fail += 1
                    print(f"FAIL  {mesh_name:10s} {arch:24s} {shape_name}: "
                          f"{type(e).__name__}: {e}")
                    traceback.print_exc(limit=3)
    print(f"\ndry-run complete: {ok} ok, {fail} failed")
    return 0 if fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
