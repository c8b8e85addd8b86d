import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST precede every other import (jax locks the device
count at first init). For each cell this script:

  with mesh:
      lowered = jax.jit(step_fn, in_shardings=…, out_shardings=…) \
          .lower(**input_specs(arch, shape))        # ShapeDtypeStructs only
      compiled = lowered.compile()
      print(compiled.memory_analysis())             # proves it fits (or not)
      print(compiled.cost_analysis())               # FLOPs/bytes → §Roofline

Because XLA's cost_analysis counts while-loop bodies once (scan
undercount — verified), the roofline inputs come from launch/hloanalysis:
jaxpr-walked global matmul FLOPs and trip-count-aware HLO collective
bytes, plus an analytic HBM-traffic model. Results cached as JSON under
results/dryrun/; benchmarks/roofline.py consumes them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
      --shape train_4k [--multi-pod] [--all] [--out results/dryrun]
"""
import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_NAMES, SHAPES, cell_runnable, get, input_specs
from repro.launch.hloanalysis import hlo_collectives, jaxpr_flops
from repro.launch.mesh import dp_size, make_production_mesh
from repro.models import LM, make_rules
from repro.models.common import spec_for, tree_specs_for_shapes
from repro.train import AdamWConfig, make_train_step
from repro.train import optimizer as opt_mod


def _microbatches(cfg, batch: int, dp: int) -> int:
    per_replica = batch // dp
    if per_replica <= 1:
        return 1
    big = cfg.param_count() > 5e10
    return per_replica if big else max(1, per_replica // 8)


def _state_specs(state_tree, p_specs, ocfg, sizes):
    if ocfg.state_dtype != "int8":
        return p_specs

    def one(leaf, spec):
        # int8 leaves are (q [*pshape[:-1], nb, 128], absmax [..., nb, 1]):
        # inherit the param's spec exactly (last-dim mapping moves to the
        # block dim) so optimizer math stays fully local
        q, s = leaf
        entries = list(spec) + [None] * (len(q.shape) - 1 - len(spec))
        nb = q.shape[-2]
        last_map = entries[len(q.shape) - 2] if len(entries) >= len(q.shape) - 1 else None
        axes_n = last_map if isinstance(last_map, tuple) else \
            ((last_map,) if last_map else ())
        tot = 1
        for a in axes_n:
            tot *= sizes.get(a, 1)
        if nb % max(tot, 1) != 0:
            entries[len(q.shape) - 2] = None
        qspec = P(*entries[: len(q.shape) - 1], None)
        return (qspec, qspec)

    return jax.tree.map(one, state_tree, p_specs,
                        is_leaf=lambda x: isinstance(x, tuple))


def _sharded_bytes(shapes_tree, specs_tree, sizes: dict) -> int:
    """Exact static per-device bytes of args given their PartitionSpecs."""
    flat_s, treedef = jax.tree.flatten(shapes_tree)
    flat_p = treedef.flatten_up_to(specs_tree)
    total = 0
    for sds, spec in zip(flat_s, flat_p):
        n = 1
        for d in sds.shape:
            n *= d
        denom = 1
        for entry in (spec or ()):  # P(...) iterates per-dim entries
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                denom *= sizes.get(a, 1)
        total += n * sds.dtype.itemsize // max(denom, 1)
    return total


def analytic_traffic(cfg, shape: str, mb: int) -> float:
    """Per-step global HBM traffic model (documented in EXPERIMENTS.md).

    train:   mb microbatches × 3 passes over weights (fwd read, bwd read,
             grad write) + 4× activation-checkpoint traffic + logits.
    prefill: one pass over weights + 2× activations.
    decode:  active weights once + full KV/state cache read (+1 slot write).
    """
    s = SHAPES[shape]
    B, S = s["batch"], s["seq"]
    pbytes = cfg.param_count() * 2
    act_ckpt = cfg.n_groups * (B // max(mb, 1)) * S * cfg.d_model * 2
    if s["kind"] == "train":
        logits = B * S * cfg.vocab_padded * 4 / max(mb, 1)
        return mb * (3 * pbytes) + mb * 4 * act_ckpt + mb * logits
    if s["kind"] == "prefill":
        return pbytes + 2 * cfg.n_groups * B * S * cfg.d_model * 2
    # decode: one token
    abytes = cfg.active_param_count() * 2
    cache = _cache_bytes(cfg, B, S)
    return abytes + cache


def _cache_bytes(cfg, B: int, S: int) -> float:
    total = 0.0
    for mixer, _ in (list(cfg.pattern) * cfg.n_groups
                     + list(cfg.pattern)[: cfg.n_tail]):
        if mixer in ("global", "bidir"):
            total += B * S * cfg.n_kv * cfg.hd * 2 * 2
        elif mixer == "local":
            total += B * min(cfg.window, S) * cfg.n_kv * cfg.hd * 2 * 2
        elif mixer == "mla":
            total += B * S * (cfg.mla.kv_lora_rank + cfg.mla.qk_rope) * 2
        elif mixer == "mamba":
            di = cfg.mamba.expand * cfg.d_model
            total += B * di * cfg.mamba.d_state * 4
        elif mixer == "rwkv":
            total += B * cfg.d_model * cfg.rwkv.head_dim * 4
    return total


def build_cell(arch: str, shape: str, multi_pod: bool, variant: dict | None = None):
    """Returns (fn, args, in_shardings, out_shardings, mesh, extra).

    variant (§Perf hillclimb knobs): fsdp=False (replicate params over
    "data" — kills per-microbatch weight all-gathers for small models),
    moe_impl="a2a" (explicit all-to-all expert parallelism),
    microbatches=N, capacity_factor=f.
    """
    import dataclasses as _dc

    variant = variant or {}
    cfg = get(arch)
    if variant.get("moe_impl"):
        cfg = _dc.replace(cfg, moe_impl=variant["moe_impl"])
    if variant.get("int8_dispatch"):
        cfg = _dc.replace(cfg, moe_int8_dispatch=True)
    if variant.get("capacity_factor") and cfg.moe:
        cfg = _dc.replace(cfg, moe=_dc.replace(
            cfg.moe, capacity_factor=variant["capacity_factor"]))
    mesh = make_production_mesh(multi_pod=multi_pod)
    sizes = dict(mesh.shape)
    a2a = variant.get("moe_impl") == "a2a"
    rules = make_rules(multi_pod=multi_pod,
                       long_context=(shape == "long_500k"), sizes=sizes,
                       decode=(SHAPES[shape]["kind"] == "decode"),
                       fsdp=variant.get("fsdp", True),
                       mesh=mesh if a2a else None, ep2d=a2a,
                       dp_only=variant.get("dp_only", False))
    lm = LM(cfg)
    specs = input_specs(cfg, shape)
    kind = SHAPES[shape]["kind"]

    axes_box = {}

    def init_params_only(key):
        params, axes = lm.init(key)
        axes_box.update(axes)
        return params

    p_shapes = jax.eval_shape(init_params_only, jax.random.key(0))
    p_specs = tree_specs_for_shapes(p_shapes, axes_box, rules.param, sizes)

    if kind == "train":
        default_sd = "int8" if cfg.param_count() > 5e10 else "fp32"
        ocfg = AdamWConfig(state_dtype=variant.get("state_dtype", default_sd))
        o_shapes = jax.eval_shape(partial(opt_mod.init_opt_state, cfg=ocfg),
                                  p_shapes)
        st = _state_specs(o_shapes["m"], p_specs, ocfg, sizes) \
            if ocfg.state_dtype == "int8" else p_specs
        o_specs = {"m": st, "v": st, "step": P()}
        mb = variant.get("microbatches") or _microbatches(
            cfg, SHAPES[shape]["batch"], dp_size(mesh))
        step = make_train_step(lm, rules, ocfg, microbatches=mb)
        batch_specs = {k: spec_for(("batch",) + (None,) * (len(v.shape) - 1),
                                   rules.act) for k, v in specs.items()}
        args = (p_shapes, o_shapes, specs)
        in_sh = (p_specs, o_specs, batch_specs)
        out_sh = (p_specs, o_specs, None)
        return step, args, in_sh, out_sh, mesh, {
            "microbatches": mb,
            "static_arg_bytes_per_device":
                _sharded_bytes(p_shapes, p_specs, sizes)
                + _sharded_bytes(o_shapes, o_specs, sizes),
            "traffic_model_bytes": analytic_traffic(cfg, shape, mb)}

    if kind == "prefill":
        def prefill_step(params, batch):
            return lm.prefill_logits(params, batch, rules)
        batch_specs = {k: spec_for(("batch",) + (None,) * (len(v.shape) - 1),
                                   rules.act) for k, v in specs.items()}
        return (prefill_step, (p_shapes, specs), (p_specs, batch_specs),
                None, mesh, {
                    "static_arg_bytes_per_device":
                        _sharded_bytes(p_shapes, p_specs, sizes),
                    "traffic_model_bytes": analytic_traffic(cfg, shape, 1)})

    # decode
    B, S = SHAPES[shape]["batch"], SHAPES[shape]["seq"]
    cache_axes_box = {}

    def init_cache_only(_):
        cache, caxes = lm.init_cache(B, S)
        cache_axes_box.update(caxes)
        return cache

    c_shapes = jax.eval_shape(init_cache_only, 0)
    c_specs = tree_specs_for_shapes(c_shapes, cache_axes_box, rules.param,
                                    sizes)

    def serve_step(params, cache, token, pos, enc_out=None):
        del enc_out  # cross-KV lives in the cache
        lg, new_cache = lm.decode_step(params, cache, token, pos, rules)
        return jnp.argmax(lg, axis=-1).astype(jnp.int32), new_cache

    tok_spec = spec_for(("batch",), rules.act)
    args = [p_shapes, c_shapes, specs["token"], specs["pos"]]
    in_sh = [p_specs, c_specs, tok_spec, P()]
    out_sh = (tok_spec, c_specs)
    if "enc_out" in specs:
        args.append(specs["enc_out"])
        in_sh.append(spec_for(("batch", None, None), rules.act))
    return (serve_step, tuple(args), tuple(in_sh), out_sh, mesh, {
        "static_arg_bytes_per_device":
            _sharded_bytes(p_shapes, p_specs, sizes)
            + _sharded_bytes(c_shapes, c_specs, sizes),
        "traffic_model_bytes": analytic_traffic(get(arch), shape, 1)})


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             force: bool = False, variant: dict | None = None,
             tag: str = "") -> dict:
    mesh_tag = "multipod" if multi_pod else "pod"
    cell_id = f"{arch}__{shape}__{mesh_tag}" + (f"__{tag}" if tag else "")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, cell_id + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    cfg = get(arch)
    ok, reason = cell_runnable(cfg, shape)
    result = {"cell": cell_id, "arch": arch, "shape": shape,
              "mesh": "2x16x16" if multi_pod else "16x16",
              "variant": variant or {}}
    if not ok:
        result.update(status="skipped", reason=reason)
        _save(path, result)
        return result
    t0 = time.time()
    try:
        fn, args, in_sh, out_sh, mesh, extra = build_cell(
            arch, shape, multi_pod, variant=variant)

        def _named(tree):
            if tree is None:
                return None
            return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                is_leaf=lambda x: isinstance(x, P))

        with mesh:
            flops_global = jaxpr_flops(fn, *args)
            t_trace = time.time() - t0
            jitted = jax.jit(fn, in_shardings=_named(in_sh),
                             out_shardings=_named(out_sh))
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0 - t_trace
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_trace - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):  # older jax: per-computation
                cost = cost[0] if cost else None
            hlo = compiled.as_text()
            coll = hlo_collectives(hlo)
        result.update(
            status="ok",
            trace_s=round(t_trace, 1), lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            flops_global=flops_global,
            flops_hlo_raw=float(cost.get("flops", -1)) if cost else -1,
            bytes_hlo_raw=float(cost.get("bytes accessed", -1)) if cost else -1,
            memory_analysis=_mem_dict(mem),
            collectives=coll,
            params=cfg.param_count(),
            active_params=cfg.active_param_count(),
            device_kind=mesh.devices.flat[0].device_kind,
            **extra,
        )
        print(f"[dryrun] {cell_id}: OK flops={flops_global:.3e} "
              f"coll/dev={coll['total_bytes']:.3e}B "
              f"(compile {t_compile:.0f}s)", flush=True)
        print(f"[dryrun] {cell_id} memory_analysis: {result['memory_analysis']}",
              flush=True)
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        result.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:])
        print(f"[dryrun] {cell_id}: FAIL {result['error']}", flush=True)
    _save(path, result)
    return result


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    out = {}
    for k in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes",
              "alias_size_in_bytes", "peak_memory_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _save(path: str, result: dict):
    with open(path, "w") as f:
        json.dump(result, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    archs = ARCH_NAMES if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    t0 = time.time()
    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                r = run_cell(arch, shape, mp, args.out, force=args.force)
                n_ok += r["status"] == "ok"
                n_skip += r["status"] == "skipped"
                n_err += r["status"] == "error"
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"in {time.time() - t0:.0f}s")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
