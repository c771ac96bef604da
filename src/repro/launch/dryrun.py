import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
# on the production mesh, prove it fits (memory_analysis + analytic budget),
# and extract roofline terms (cost_analysis + collective parse).
#
# MUST run as its own process (the two lines above must execute before any
# jax initialization - do not import this module into a live jax process).
#
# Cost-model calibration: XLA counts a while-loop body ONCE regardless of
# trip count (verified in tests/test_roofline.py). Every loop in this model
# stack (layer scan, chunked-attention KV scan, recurrent time scans) carries
# an unroll knob, so we lower the cell at knob=1 and knob=2 and solve for the
# per-iteration cost; totals are exact linear reconstructions:
#
#   c(base)       = out + ls + a + s      (one body instance each)
#   c(layer x2)   = out + 2(ls + a + s)
#   c(attn  x2)   = out + ls + 2a + s
#   c(ssm   x2)   = out + ls + a + 2s
#   total         = out + R*ls + R*Ta*a + R*Ts*s
#
# where R = layer-scan trips, Ta = chunked-attn trips, Ts = time-scan trips.
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out reports/dryrun]

import argparse
import dataclasses
import json
import math
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, cell_is_runnable, get_config
from repro.configs.base import InputShape, ModelConfig
from repro.launch.mesh import make_production_mesh
from repro.launch import roofline as R
from repro.models import model as M
from repro.models.blocks import cache_len
from repro.models.layers import kv_chunks
from repro.models.frontends import num_frontend_embeds
from repro.parallel import sharding as S
from repro.training import optimizer as O


# ---------------------------------------------------------------------------
# abstract inputs (ShapeDtypeStruct only - no allocation)
# ---------------------------------------------------------------------------


def params_struct(cfg: ModelConfig):
    return jax.eval_shape(lambda k: M.init_params(cfg, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    """Abstract stand-ins for every model input of this cell."""
    B, Ssz = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        batch = {"tokens": jax.ShapeDtypeStruct((B, Ssz + 1), jnp.int32)}
        if cfg.frontend == "vision":
            batch["extra_embeds"] = jax.ShapeDtypeStruct(
                (B, num_frontend_embeds(cfg), cfg.d_model), jnp.dtype(cfg.dtype))
        return {"batch": batch}
    if shape.kind == "prefill":
        cache = jax.eval_shape(lambda: M.init_cache(cfg, B, Ssz))
        spec = {"tokens": jax.ShapeDtypeStruct((B, Ssz), jnp.int32), "cache": cache}
        if cfg.frontend == "vision":
            spec["extra_embeds"] = jax.ShapeDtypeStruct(
                (B, num_frontend_embeds(cfg), cfg.d_model), jnp.dtype(cfg.dtype))
        return spec
    # decode: one new token against a cache of shape.seq_len
    cache = jax.eval_shape(lambda: M.init_cache(cfg, B, Ssz))
    return {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32), "cache": cache}


def make_step(cfg: ModelConfig, shape: InputShape, opt_cfg: O.OptConfig):
    if shape.kind == "train":
        def train_step(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                M.loss_fn, has_aux=True)(params, batch, cfg)
            params, opt_state, om = O.apply_updates(params, grads, opt_state, opt_cfg)
            return params, opt_state, (loss, om["grad_norm"])
        return train_step
    if shape.kind == "prefill":
        def prefill_step(params, tokens, cache, extra_embeds=None):
            return M.prefill(params, tokens, cfg, cache, extra_embeds=extra_embeds)
        return prefill_step

    def serve_step(params, tokens, cache):
        return M.decode_step(params, tokens, cfg, cache)
    return serve_step


# ---------------------------------------------------------------------------
# lowering one variant
# ---------------------------------------------------------------------------


def lower_cell(cfg: ModelConfig, shape: InputShape, mesh,
               opt_cfg: Optional[O.OptConfig] = None):
    """Returns the lowered step for this cfg variant on this mesh."""
    opt_cfg = opt_cfg or O.OptConfig(moment_dtype=cfg.optimizer_state_dtype)
    ba = S.batch_axes(mesh)
    nb = 1
    for a in ba:
        nb *= mesh.shape[a]
    if cfg.batch_axes is not None:
        ba = tuple(cfg.batch_axes)  # explicit variant override
        nb = 1
        for a in ba:
            nb *= mesh.shape[a]
    elif shape.global_batch % nb == 0 and shape.global_batch >= nb:
        cfg = dataclasses.replace(cfg, batch_axes=tuple(ba))
    specs = input_specs(cfg, shape)
    step = make_step(cfg, shape, opt_cfg)
    n_b = nb  # input batch sharding follows cfg.batch_axes (variant-aware)
    from jax.sharding import NamedSharding, PartitionSpec as P

    def ns(spec):
        return NamedSharding(mesh, spec)

    def batch_sharding(x):
        b_ok = x.shape[0] % n_b == 0 and x.shape[0] >= n_b
        return ns(P(ba if b_ok else None, *([None] * (x.ndim - 1))))

    p_struct = params_struct(cfg)
    p_shard = S.param_shardings(p_struct, mesh, cfg.param_mode)

    with mesh:
        if shape.kind == "train":
            o_struct = jax.eval_shape(lambda p: O.init(p, opt_cfg), p_struct)
            o_shard = O.OptState(step=ns(P()),
                                 mu=S.param_shardings(p_struct, mesh, cfg.param_mode),
                                 nu=S.param_shardings(p_struct, mesh, cfg.param_mode))
            b_shard = jax.tree_util.tree_map(batch_sharding, specs["batch"])
            jitted = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                             donate_argnums=(0, 1))
            return jitted.lower(p_struct, o_struct, specs["batch"])
        c_struct = specs["cache"]
        c_shard = jax.tree_util.tree_map(
            ns, S.cache_specs_for(mesh, c_struct, shape.global_batch))
        t_shard = batch_sharding(specs["tokens"])
        if shape.kind == "prefill":
            args = [p_struct, specs["tokens"], c_struct]
            in_sh = [p_shard, t_shard, c_shard]
            if "extra_embeds" in specs:
                args.append(specs["extra_embeds"])
                in_sh.append(batch_sharding(specs["extra_embeds"]))
            jitted = jax.jit(step, in_shardings=tuple(in_sh), donate_argnums=(2,))
            return jitted.lower(*args)
        jitted = jax.jit(step, in_shardings=(p_shard, t_shard, c_shard),
                         donate_argnums=(2,))
        return jitted.lower(p_struct, specs["tokens"], c_struct)


# ---------------------------------------------------------------------------
# loop trip counts per cell (must mirror model dispatch exactly)
# ---------------------------------------------------------------------------


def trip_counts(cfg: ModelConfig, shape: InputShape) -> Dict[str, int]:
    trips = {"layer": cfg.pattern_repeats, "attn": 0, "ssm": 0}
    Ssz = shape.seq_len
    if shape.kind == "prefill":
        s_q = Ssz + (num_frontend_embeds(cfg) if cfg.frontend == "vision" else 0)
        t_cache = cache_len(cfg, Ssz)
        if any(k in ("dense", "moe", "hymba") for k in cfg.block_pattern):
            trips["attn"] = kv_chunks(s_q, t_cache, cfg.attn_chunk_kv)
    s_time = Ssz if shape.kind in ("train", "prefill") else 1
    if shape.kind == "train":
        s_time = Ssz  # loss_fn trains on tokens[:, :-1] -> S positions
        if cfg.frontend == "vision":
            s_time += num_frontend_embeds(cfg)
    if s_time > 1:
        if any(k in ("mlstm", "slstm") for k in cfg.block_pattern):
            trips["ssm"] = s_time
        if "hymba" in cfg.block_pattern:
            trips["ssm"] = -(-s_time // min(cfg.ssd_chunk, s_time))
    return trips


def _measure_cfg(cfg: ModelConfig, shape: InputShape, mesh) -> Dict[str, Any]:
    """Lower/compile at each active knob and reconstruct true per-chip costs."""
    trips = trip_counts(cfg, shape)
    variants = {"base": cfg}
    if trips["layer"] > 1:
        variants["layer"] = dataclasses.replace(cfg, scan_unroll=2)
    if trips["attn"] > 1:
        variants["attn"] = dataclasses.replace(cfg, attn_scan_unroll=2)
    if trips["ssm"] > 1:
        variants["ssm"] = dataclasses.replace(cfg, time_scan_unroll=2)

    meas: Dict[str, Dict[str, float]] = {}
    base_compiled = None
    for name, vcfg in variants.items():
        lowered = lower_cell(vcfg, shape, mesh)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        wire = R.collective_wire_bytes(compiled.as_text())
        meas[name] = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "transcendentals": float(cost.get("transcendentals", 0.0)),
            **{f"wire_{k}": wire[k] for k in
               ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")},
            "wire_total": wire["total"],
            "collective_ops": wire["ops"],
        }
        if name == "base":
            base_compiled = compiled

    keys = [k for k in meas["base"] if k != "collective_ops"]
    base = meas["base"]
    slopes = {}
    for knob in ("layer", "attn", "ssm"):
        if knob in meas:
            slopes[knob] = {k: meas[knob][k] - base[k] for k in keys}
        else:
            slopes[knob] = {k: 0.0 for k in keys}
    total = {}
    for k in keys:
        ls_pure = slopes["layer"][k] - slopes["attn"][k] - slopes["ssm"][k]
        out = base[k] - slopes["layer"][k]
        total[k] = (out + trips["layer"] * ls_pure
                    + trips["layer"] * max(1, trips["attn"]) * slopes["attn"][k]
                    + trips["layer"] * max(1, trips["ssm"]) * slopes["ssm"][k])
        total[k] = max(total[k], base[k])  # guard tiny negative extrapolation
    return {"trips": trips, "raw": meas, "corrected": total,
            "compiled": base_compiled}


# ---------------------------------------------------------------------------
# analytic per-chip memory budget (TPU-true; CPU memory_analysis is approximate)
# ---------------------------------------------------------------------------


def analytic_memory(cfg: ModelConfig, shape: InputShape, mesh) -> Dict[str, float]:
    p_struct = params_struct(cfg)
    specs = S.param_specs(p_struct)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def shard_div(spec):
        d = 1
        for entry in spec:
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            for nme in names:
                d *= axis_sizes[nme]
        return d

    def bytes_of(tree, spec_tree):
        flat, _ = jax.tree_util.tree_flatten(tree)
        sflat, _ = jax.tree_util.tree_flatten(
            spec_tree, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        tot = 0.0
        for leaf, spec in zip(flat, sflat):
            tot += math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize / shard_div(spec)
        return tot

    param_b = bytes_of(p_struct, specs)
    out = {"params": param_b}
    if shape.kind == "train":
        mom = jnp.dtype(cfg.optimizer_state_dtype).itemsize
        out["optimizer"] = 2 * param_b * mom / jnp.dtype(cfg.dtype).itemsize
        out["grads_transient"] = param_b * 4 / jnp.dtype(cfg.dtype).itemsize
        n_b = math.prod([axis_sizes[a] for a in S.batch_axes(mesh)])
        b_loc = max(1, shape.global_batch // n_b)
        # remat residuals: one [B,S,D] per super-layer + current layer temps
        out["residuals"] = (cfg.pattern_repeats * b_loc * shape.seq_len
                            * cfg.d_model * jnp.dtype(cfg.dtype).itemsize)
        v_shard = axis_sizes.get("model", 1)
        out["logits_f32"] = b_loc * shape.seq_len * cfg.vocab_size * 4 / v_shard
    else:
        cache = jax.eval_shape(lambda: M.init_cache(cfg, shape.global_batch,
                                                    shape.seq_len))
        cspecs = S.cache_specs_for(mesh, cache, shape.global_batch)
        out["kv_cache"] = bytes_of(cache, cspecs)
    out["total"] = sum(v for k, v in out.items())
    return out


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             mesh=None, verbose: bool = True,
             overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    t0 = time.time()
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    result: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                              "mesh": mesh_name, "ok": False}
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        result.update(skipped=True, reason=why, ok=True)
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: SKIP ({why})")
        return result
    result["overrides"] = overrides or {}
    try:
        mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
        m = _measure_cfg(cfg, shape, mesh)
        compiled = m.pop("compiled")
        try:
            mem = compiled.memory_analysis()
            result["memory"] = {
                k: int(getattr(mem, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "peak_memory_in_bytes")
                if hasattr(mem, k)}
        except Exception as e:
            result["memory"] = {"error": str(e)}
        result["memory_analytic"] = analytic_memory(cfg, shape, mesh)
        c = m["corrected"]
        cost = {"flops": c["flops"], "bytes accessed": c["bytes"],
                "transcendentals": c["transcendentals"]}
        terms = {
            "flops_per_chip": c["flops"],
            "bytes_per_chip": c["bytes"],
            "wire_bytes_per_chip": c["wire_total"],
            "wire_breakdown": {k: c[f"wire_{k}"] for k in
                               ("all-reduce", "all-gather", "reduce-scatter",
                                "all-to-all", "collective-permute")},
            "collective_ops": m["raw"]["base"]["collective_ops"],
            "compute_s": c["flops"] / R.PEAK_FLOPS,
            "memory_s": c["bytes"] / R.HBM_BW,
            "collective_s": c["wire_total"] / (R.LINK_BW * 2),
        }
        terms["dominant"] = max(
            [("compute", terms["compute_s"]), ("memory", terms["memory_s"]),
             ("collective", terms["collective_s"])], key=lambda kv: kv[1])[0]
        terms["step_s_lower_bound"] = max(terms["compute_s"], terms["memory_s"],
                                          terms["collective_s"])
        # useful-FLOPs ratio
        p_struct = params_struct(cfg)
        n_total = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(p_struct))
        n_active = _active_params(cfg, p_struct)
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        mf = R.model_flops(n_active, tokens, shape.kind)
        n_chips = 512 if multi_pod else 256
        terms["model_flops_global"] = mf
        hlo_global = terms["flops_per_chip"] * n_chips
        terms["useful_flops_ratio"] = mf / hlo_global if hlo_global else 0.0
        terms["n_params"] = n_total
        terms["n_active_params"] = n_active
        result["trips"] = m["trips"]
        result["raw"] = m["raw"]  # per-knob measurements (slope analysis)
        result["roofline"] = terms
        result["compile_seconds"] = time.time() - t0
        result["ok"] = True
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: OK "
                  f"compute={terms['compute_s']:.4f}s memory={terms['memory_s']:.4f}s "
                  f"collective={terms['collective_s']:.4f}s dominant={terms['dominant']} "
                  f"useful={terms['useful_flops_ratio']:.2f} "
                  f"(compile {result['compile_seconds']:.0f}s)")
    except Exception as e:
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: FAIL {result['error']}")
    return result


def _active_params(cfg: ModelConfig, p_struct) -> int:
    flat = jax.tree_util.tree_flatten_with_path(p_struct)[0]
    active = 0
    for path, leaf in flat:
        size = math.prod(leaf.shape)
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        if "/moe/" in pstr and "router" not in pstr:
            active += size * cfg.num_experts_per_tok // max(1, cfg.num_experts)
        else:
            active += size
    return active


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--variant", default=None,
                    help="cfg overrides key=val[,key=val...], e.g. "
                         "param_mode=tp or moe_groups=16 (named in output)")
    ap.add_argument("--tag", default=None, help="suffix for the output file")
    args = ap.parse_args()
    overrides = {}
    if args.variant:
        import ast
        for kv in args.variant.split(";"):
            k, v = kv.split("=", 1)
            try:
                overrides[k] = ast.literal_eval(v)
            except (ValueError, SyntaxError):
                overrides[k] = v

    os.makedirs(args.out, exist_ok=True)
    if args.all:
        cells = [(a, s) for a in ARCHS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
    n_fail = 0
    for arch, shape in cells:
        res = run_cell(arch, shape, multi_pod=args.multi_pod, mesh=mesh,
                       overrides=overrides)
        tag = f"__{args.tag}" if args.tag else ""
        fname = f"{arch.replace('-', '_')}__{shape}__{mesh_name}{tag}.json"
        with open(os.path.join(args.out, fname), "w") as f:
            json.dump(res, f, indent=1)
        n_fail += 0 if res["ok"] else 1
    print(f"[dryrun] done: {len(cells) - n_fail}/{len(cells)} cells OK")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
