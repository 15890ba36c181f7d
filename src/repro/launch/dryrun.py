import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("REPRO_REMAT_POLICY", "nothing")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: for each cell we
build ShapeDtypeStruct stand-ins (zero allocation), jit with explicit
in_shardings from the rule trees, .lower().compile() against the production
mesh, and record memory_analysis / cost_analysis / parsed collective bytes
into reports/dryrun/<cell>.json for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod-only|--single-only]
  PYTHONPATH=src python -m repro.launch.dryrun --all --skip-done   # resume
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.analysis import roofline as rl
from repro.configs import CONFIGS, applicable_shapes
from repro.configs.base import ModelConfig, ShapeSpec
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import batch_pspec, cache_pspec, state_pspec, to_shardings
from repro.models import decode_step, init_cache, init_params, prefill
from repro.train import adamw, make_train_step
from repro.train.train_step import TrainState

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "reports", "dryrun")


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; weak-type-correct, no allocation)
# ---------------------------------------------------------------------------

def _sds(tree, shardings=None):
    if shardings is None:
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
        )
    return jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, shardings
    )


def shape_adjusted_config(cfg: ModelConfig, shape: ShapeSpec) -> ModelConfig:
    """Per-shape config tweaks that only affect table sizes, not structure."""
    kw: Dict[str, Any] = {}
    if cfg.pos_embedding == "learned" and shape.seq_len + 1 > cfg.max_target_positions:
        kw["max_target_positions"] = shape.seq_len + 1
    if cfg.moe is not None:
        # bound dispatch-tensor memory: small groups at scale
        gs = 512 if cfg.moe.num_experts >= 128 else 2048
        kw["moe"] = dataclasses.replace(cfg.moe, group_size=gs)
    return dataclasses.replace(cfg, **kw) if kw else cfg


def input_specs(cfg: ModelConfig, shape: ShapeSpec, mesh) -> Dict[str, Any]:
    """ShapeDtypeStructs + shardings for every model input of this cell."""
    B = shape.global_batch
    S = shape.seq_len
    dt = jnp.bfloat16
    batch: Dict[str, Any] = {}
    if shape.kind == "train":
        S_text = S - (cfg.num_prefix_tokens if cfg.frontend == "vision_stub" else 0)
        batch["tokens"] = jax.ShapeDtypeStruct((B, S_text), jnp.int32)
        batch["labels"] = jax.ShapeDtypeStruct((B, S_text), jnp.int32)
        if cfg.frontend == "vision_stub":
            batch["prefix_embed"] = jax.ShapeDtypeStruct(
                (B, cfg.num_prefix_tokens, cfg.d_model), dt
            )
        if cfg.family == "encdec":
            batch["audio_frames"] = jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model), dt)
    elif shape.kind == "prefill":
        S_text = S - (cfg.num_prefix_tokens if cfg.frontend == "vision_stub" else 0)
        batch["tokens"] = jax.ShapeDtypeStruct((B, S_text), jnp.int32)
        if cfg.frontend == "vision_stub":
            batch["prefix_embed"] = jax.ShapeDtypeStruct(
                (B, cfg.num_prefix_tokens, cfg.d_model), dt
            )
        if cfg.family == "encdec":
            batch["audio_frames"] = jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model), dt)
    else:  # decode / long_decode
        batch["tokens"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    specs = batch_pspec(mesh, batch)
    return _sds(batch, to_shardings(mesh, specs))


def _state_structs(cfg: ModelConfig, mesh, *, moment_dtype=jnp.bfloat16):
    opt = adamw(1e-4, moment_dtype=moment_dtype)

    def make():
        params = init_params(cfg, jax.random.PRNGKey(0))
        return TrainState(params=params, opt_state=opt.init(params))

    state_shapes = jax.eval_shape(make)
    pspec = state_pspec(mesh, state_shapes)
    return _sds(state_shapes, to_shardings(mesh, pspec)), opt


def _param_structs(cfg: ModelConfig, mesh):
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    pspec = state_pspec(mesh, shapes)
    return _sds(shapes, to_shardings(mesh, pspec))


def _cache_structs(cfg: ModelConfig, mesh, batch: int, max_len: int, *, with_cross: bool):
    def make():
        c = init_cache(cfg, batch, max_len, cache_dtype=jnp.bfloat16)
        if with_cross and cfg.family == "encdec":
            K, hd = cfg.n_kv_heads, cfg.hd
            cross = {
                "k": jnp.zeros((cfg.n_layers, batch, cfg.encoder_seq, K, hd), jnp.bfloat16),
                "v": jnp.zeros((cfg.n_layers, batch, cfg.encoder_seq, K, hd), jnp.bfloat16),
            }
            c["decoder"] = {"self": c["decoder"]["self"], "cross": cross}
        return c

    shapes = jax.eval_shape(make)
    pspec = cache_pspec(mesh, cfg, shapes)
    return _sds(shapes, to_shardings(mesh, pspec))


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------

def lower_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    microbatches: int = 1,
    remat: bool = True,
    moe_group: Optional[int] = None,
) -> Tuple[Any, Any, ModelConfig, ShapeSpec]:
    """Returns (lowered, compiled, cfg, shape)."""
    from repro.configs.base import SHAPES

    cfg0 = CONFIGS[arch]
    shape = SHAPES[shape_name]
    cfg = shape_adjusted_config(cfg0, shape)
    if moe_group is not None and cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, group_size=moe_group))
    mesh = make_production_mesh(multi_pod=multi_pod)

    with jax.set_mesh(mesh):
        batch_structs = input_specs(cfg, shape, mesh)
        if shape.kind == "train":
            state_structs, opt = _state_structs(cfg, mesh)
            step = make_train_step(cfg, opt, remat=remat, microbatches=microbatches)
            jitted = jax.jit(step, donate_argnums=(0,))
            lowered = jitted.lower(state_structs, batch_structs)
        elif shape.kind == "prefill":
            params_structs = _param_structs(cfg, mesh)
            cache_structs = _cache_structs(
                cfg, mesh, shape.global_batch, shape.seq_len, with_cross=False
            )
            fn = lambda p, b, c: prefill(p, cfg, b, c)  # noqa: E731
            jitted = jax.jit(fn, donate_argnums=(2,))
            lowered = jitted.lower(params_structs, batch_structs, cache_structs)
        else:  # decode / long_decode
            params_structs = _param_structs(cfg, mesh)
            cache_structs = _cache_structs(
                cfg, mesh, shape.global_batch, shape.seq_len, with_cross=True
            )
            fn = lambda p, t, c, l: decode_step(p, cfg, t, c, l)  # noqa: E731
            jitted = jax.jit(fn, donate_argnums=(2,))
            lowered = jitted.lower(
                params_structs,
                batch_structs["tokens"],
                cache_structs,
                jax.ShapeDtypeStruct((), jnp.int32),
            )
        compiled = lowered.compile()
    return lowered, compiled, cfg, shape


# ---------------------------------------------------------------------------
# depth-probe cost extraction
#
# XLA's cost_analysis counts a while-loop body ONCE regardless of trip count,
# so a rolled layer-scan undercounts FLOPs/collectives by ~n_layers; a fully
# unrolled compile counts correctly but is too slow for 126-layer models and
# degrades buffer-reuse stats.  Instead: compile the FULL model rolled (the
# production program — memory stats + compile proof) plus a few *small
# unrolled depth probes*; per-stage layer costs follow from a linear solve
#     cost(probe) = outside + sum_i counts_i * body_i
# and total = outside + sum_i full_counts_i * body_i.  Exact for homogeneous
# stages (every layer in a stage lowers identically).
# ---------------------------------------------------------------------------

def probe_plans(cfg: ModelConfig):
    """Returns (probes, full_counts): probes = [(cfg_variant, counts)], where
    counts maps stage name -> #stage-units in that variant."""
    import dataclasses as dc

    fam = cfg.family
    if fam in ("dense", "vlm"):
        p = cfg.global_every if (cfg.sliding_window and cfg.global_every) else 1
        return (
            [
                (dc.replace(cfg, n_layers=p), {"dec": 1}),
                (dc.replace(cfg, n_layers=2 * p), {"dec": 2}),
            ],
            {"dec": cfg.n_layers // p},
        )
    if fam == "moe":
        nd = cfg.moe.num_dense_layers
        if nd == 0:
            return (
                [
                    (dc.replace(cfg, n_layers=1), {"moe": 1}),
                    (dc.replace(cfg, n_layers=2), {"moe": 2}),
                ],
                {"moe": cfg.n_layers},
            )
        m1 = dc.replace(cfg.moe, num_dense_layers=1)
        m2 = dc.replace(cfg.moe, num_dense_layers=2)
        return (
            [
                (dc.replace(cfg, n_layers=2, moe=m1), {"dense": 1, "moe": 1}),
                (dc.replace(cfg, n_layers=3, moe=m2), {"dense": 2, "moe": 1}),
                (dc.replace(cfg, n_layers=3, moe=m1), {"dense": 1, "moe": 2}),
            ],
            {"dense": nd, "moe": cfg.n_layers - nd},
        )
    if fam == "hybrid":
        per = cfg.shared_attn_every
        n_super = cfg.n_layers // per
        n_tail = cfg.n_layers - n_super * per
        probes = [
            (dc.replace(cfg, n_layers=per + 2), {"super": 1, "tail": 2}),
            (dc.replace(cfg, n_layers=2 * per + 2), {"super": 2, "tail": 2}),
            (dc.replace(cfg, n_layers=per + 4), {"super": 1, "tail": 4}),
        ]
        return probes, {"super": n_super, "tail": n_tail}
    if fam == "ssm":
        per = cfg.xlstm.slstm_every
        return (
            [
                (dc.replace(cfg, n_layers=per), {"group": 1}),
                (dc.replace(cfg, n_layers=2 * per), {"group": 2}),
            ],
            {"group": cfg.n_layers // per},
        )
    if fam == "encdec":
        return (
            [
                (dc.replace(cfg, n_layers=1, n_encoder_layers=1), {"enc": 1, "dec": 1}),
                (dc.replace(cfg, n_layers=1, n_encoder_layers=2), {"enc": 2, "dec": 1}),
                (dc.replace(cfg, n_layers=2, n_encoder_layers=1), {"enc": 1, "dec": 2}),
            ],
            {"enc": cfg.n_encoder_layers, "dec": cfg.n_layers},
        )
    raise ValueError(fam)


def _lower_variant(
    cfg: ModelConfig, shape: ShapeSpec, mesh, *, microbatches=1, remat=True, compile=True
):
    """Lower (and optionally compile) one config variant for the given shape."""
    with jax.set_mesh(mesh):
        batch_structs = input_specs(cfg, shape, mesh)
        if shape.kind == "train":
            state_structs, opt = _state_structs(cfg, mesh)
            step = make_train_step(cfg, opt, remat=remat, microbatches=microbatches)
            jitted = jax.jit(step, donate_argnums=(0,))
            lowered = jitted.lower(state_structs, batch_structs)
        elif shape.kind == "prefill":
            params_structs = _param_structs(cfg, mesh)
            cache_structs = _cache_structs(
                cfg, mesh, shape.global_batch, shape.seq_len, with_cross=False
            )
            fn = lambda p, b, c: prefill(p, cfg, b, c)  # noqa: E731
            jitted = jax.jit(fn, donate_argnums=(2,))
            lowered = jitted.lower(params_structs, batch_structs, cache_structs)
        else:
            params_structs = _param_structs(cfg, mesh)
            cache_structs = _cache_structs(
                cfg, mesh, shape.global_batch, shape.seq_len, with_cross=True
            )
            fn = lambda p, t, c, l: decode_step(p, cfg, t, c, l)  # noqa: E731
            jitted = jax.jit(fn, donate_argnums=(2,))
            lowered = jitted.lower(
                params_structs,
                batch_structs["tokens"],
                cache_structs,
                jax.ShapeDtypeStruct((), jnp.int32),
            )
        return lowered, (lowered.compile() if compile else None)


def _probe_metrics(variant, shape, mesh, n_dev: int, **lower_kw) -> Dict[str, float]:
    """Per-probe metrics via two lowerings:
      A) fully unrolled (layers + inner kernel scans), *lowered only* —
         cost_analysis on the unpartitioned module counts every layer and
         every kernel-scan iteration; global values are divided by n_dev;
      B) layer-unrolled / inner-rolled, *compiled* — small graph, fast CPU
         codegen; the partitioned HLO text yields collective wire bytes
         (inner kernel scans contain no collectives)."""
    os.environ["REPRO_SCAN_UNROLL"] = "full"
    os.environ["REPRO_INNER_UNROLL"] = "full"
    lowered, _ = _lower_variant(variant, shape, mesh, compile=False, **lower_kw)
    cost = lowered.cost_analysis() or {}
    out = {
        "flops": float(cost.get("flops", 0.0)) / n_dev,
        "bytes": float(cost.get("bytes accessed", 0.0)) / n_dev,
    }
    os.environ["REPRO_INNER_UNROLL"] = "1"
    _, compiled = _lower_variant(variant, shape, mesh, compile=True, **lower_kw)
    colls = rl.parse_collectives(compiled.as_text(), n_dev)
    out["coll"] = colls.wire_bytes
    for op, v in colls.by_op.items():
        out[f"coll_{op}"] = v
    return out


def solve_stage_costs(
    probe_counts, probe_metrics, full_counts
) -> Dict[str, float]:
    """Least-squares solve cost = outside + sum_i counts_i*body_i, then
    extrapolate to full depth.  Returns totals per metric key."""
    stages = sorted(full_counts)
    keys = sorted({k for m in probe_metrics for k in m})
    A = np.array(
        [[1.0] + [float(c.get(s, 0)) for s in stages] for c in probe_counts]
    )
    totals: Dict[str, float] = {}
    for key in keys:
        b = np.array([m.get(key, 0.0) for m in probe_metrics])
        x, *_ = np.linalg.lstsq(A, b, rcond=None)
        outside = max(x[0], 0.0)
        bodies = {s: max(x[1 + i], 0.0) for i, s in enumerate(stages)}
        totals[key] = outside + sum(full_counts[s] * bodies[s] for s in stages)
    return totals


def analyze_cell(arch: str, shape_name: str, *, multi_pod: bool, **kw) -> Dict[str, Any]:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    n_dev = 512 if multi_pod else 256

    # 1) full model, rolled scans: the production compile (memory + proof)
    os.environ["REPRO_SCAN_UNROLL"] = "1"
    t0 = time.time()
    lowered, compiled, cfg, shape = lower_cell(arch, shape_name, multi_pod=multi_pod, **kw)
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    rolled_cost = compiled.cost_analysis() or {}
    hlo_lines = compiled.as_text().count("\n")

    # 2) depth probes: exact per-stage costs (see _probe_metrics)
    mesh = make_production_mesh(multi_pod=multi_pod)
    probes, full_counts = probe_plans(cfg)
    probe_counts, probe_mets = [], []
    t1 = time.time()
    probe_kw = {k: v for k, v in kw.items() if k in ("microbatches", "remat")}
    for variant, counts in probes:
        probe_counts.append(counts)
        probe_mets.append(_probe_metrics(variant, shape, mesh, n_dev, **probe_kw))
    probe_s = time.time() - t1
    os.environ["REPRO_SCAN_UNROLL"] = "1"
    os.environ["REPRO_INNER_UNROLL"] = "1"
    totals = solve_stage_costs(probe_counts, probe_mets, full_counts)

    colls_by_op = {
        k[len("coll_"):]: v for k, v in totals.items() if k.startswith("coll_")
    }
    cost = {"flops": totals["flops"], "bytes accessed": totals["bytes"]}
    coll_total = totals["coll"]

    total_p, active_p = cfg.param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind in ("train", "prefill") else 1)
    mf = rl.model_flops_per_step(total_p, active_p, tokens, "train" if shape.kind == "train" else "serve")

    roof = rl.Roofline(
        arch=arch,
        shape=shape_name,
        mesh=mesh_name,
        n_devices=n_dev,
        hlo_flops_per_device=float(cost.get("flops", 0.0)),
        hlo_bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        collective_bytes_per_device=coll_total,
        model_flops=mf,
        collective_by_op=colls_by_op,
        collective_counts={},
        memory_stats={
            "argument_bytes": mem.argument_size_in_bytes if mem else -1,
            "output_bytes": mem.output_size_in_bytes if mem else -1,
            "temp_bytes": mem.temp_size_in_bytes if mem else -1,
            "alias_bytes": mem.alias_size_in_bytes if mem else -1,
        },
    ).finalize()

    out = roof.to_dict()
    out["compile_s"] = compile_s
    out["probe_s"] = probe_s
    out["rolled_flops_per_device"] = float(rolled_cost.get("flops", 0.0))
    out["hlo_lines"] = hlo_lines
    out["total_params"] = total_p
    out["active_params"] = active_p
    out["tokens_per_step"] = tokens
    print(
        f"[{arch} x {shape_name} x {mesh_name}] compile={compile_s:.1f}s "
        f"flops/dev={out['hlo_flops_per_device']:.3e} bytes/dev={out['hlo_bytes_per_device']:.3e} "
        f"coll/dev={out['collective_bytes_per_device']:.3e} dominant={out['dominant']} "
        f"args={out['memory_stats']['argument_bytes']/1e9:.2f}GB temp={out['memory_stats']['temp_bytes']/1e9:.2f}GB"
    )
    print(f"  memory_analysis: {mem}")
    print(f"  terms: compute={out['compute_s']*1e3:.2f}ms memory={out['memory_s']*1e3:.2f}ms "
          f"collective={out['collective_s']*1e3:.2f}ms useful_ratio={out['useful_ratio']:.3f} "
          f"roofline_fraction={out['roofline_fraction']:.3f}")
    return out


def cell_path(arch: str, shape_name: str, mesh_name: str) -> str:
    os.makedirs(REPORT_DIR, exist_ok=True)
    return os.path.join(REPORT_DIR, f"{arch}__{shape_name}__{mesh_name}.json")


def run_all(args) -> None:
    cells = []
    for arch, cfg in CONFIGS.items():
        if args.arch and arch != args.arch:
            continue
        for shape in applicable_shapes(cfg):
            if args.shape and shape.name != args.shape:
                continue
            meshes = []
            if not args.multipod_only:
                meshes.append(False)
            if not args.single_only:
                meshes.append(True)
            for mp in meshes:
                cells.append((arch, shape.name, mp))
    print(f"{len(cells)} cells to run")
    failures = []
    for arch, shape_name, mp in cells:
        mesh_name = "2x16x16" if mp else "16x16"
        path = cell_path(arch, shape_name, mesh_name)
        if args.skip_done and os.path.exists(path):
            print(f"skip done: {arch} x {shape_name} x {mesh_name}")
            continue
        try:
            out = analyze_cell(arch, shape_name, multi_pod=mp)
            with open(path, "w") as f:
                json.dump(out, f, indent=1)
        except Exception as e:  # noqa: BLE001
            print(f"FAILED: {arch} x {shape_name} x {mesh_name}: {e}")
            traceback.print_exc()
            failures.append((arch, shape_name, mesh_name, str(e)))
    print(f"\ndone; {len(failures)} failures")
    for f in failures:
        print("  FAIL:", f[:3])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true", help="single cell: use 2x16x16")
    ap.add_argument("--multipod-only", action="store_true")
    ap.add_argument("--single-only", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()
    if args.all or (args.arch and not args.shape) or (args.shape and not args.arch):
        run_all(args)
    else:
        out = analyze_cell(args.arch, args.shape, multi_pod=args.multipod)
        mesh_name = "2x16x16" if args.multipod else "16x16"
        with open(cell_path(args.arch, args.shape, mesh_name), "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
