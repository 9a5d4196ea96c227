import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production meshes and extract memory / cost / collective statistics.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out.json]
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --shape decode_32k --spec

The first two lines of this file pin 512 placeholder CPU devices BEFORE any
jax import so jax.make_mesh can build the (2, 16, 16) production mesh.
"""
import argparse
import json
import re
import sys
from collections import Counter

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import configs
from ..configs.base import ShapeConfig, shapes_for
from ..distributed import sharding as shd
from ..models import registry
from .compile_cache import enable_compile_cache
from .mesh import make_production_mesh

# hardware constants (TPU v5e)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9
HBM_BYTES = 16e9

_COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^\n]*?\s*([a-z0-9]+)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str):
    """Sum per-device operand bytes of every collective op in (partitioned) HLO."""
    dtype_bytes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                   "f64": 8, "s8": 1, "u8": 1, "pred": 1, "s64": 8, "f8e4m3": 1}
    total = 0
    counts = Counter()
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*([a-z0-9]+)\[([0-9,]*)\][^=]*\b"
                      r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                      r"collective-permute)", line)
        if not m:
            continue
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * dtype_bytes.get(dt, 4)
        counts[kind] += 1
    return total, dict(counts)


def _cpu_upcast_artifact(hlo_text: str) -> int:
    """Bytes of loop-carried f32 copies of bf16 buffers that the CPU backend
    introduces (it has no native bf16 dot, so LICM hoists whole-array f32
    converts out of the layer scan).  These cannot occur on the TPU backend
    (native bf16 MXU) — see EXPERIMENTS §Dry-run.  Detected as >=0.5 GB f32
    dynamic-update-slice buffers (the f32 shadow copies of scanned caches)."""
    total = 0
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*f32\[([0-9,]+)\][^=]*\bdynamic-update-slice", line)
        if not m:
            continue
        n = 4
        for d in m.group(1).split(","):
            n *= int(d)
        if n >= 5e8:  # each op line is one distinct f32 shadow buffer
            total += n
    return total


def _weight_mode(cfg, shape_kind: str, mesh) -> str:
    """Serving prefers TP weights (replicated over data) when they fit;
    decode uses the context-parallel attention sharding (tp_seq)."""
    if shape_kind == "train":
        return "fsdp"
    tp = mesh.shape["model"]
    per_chip = registry.param_bytes(cfg) / tp
    if per_chip >= 0.55 * HBM_BYTES:
        return "fsdp"
    return "tp_seq" if shape_kind == "decode" else "tp"


def build_cell(arch: str, shape: ShapeConfig, mesh, *, spec_decode=False,
               remat: str = "full", cfg_override=None, accum_override=None):
    """Returns (fn, args, in_shardings, out_shardings-ish, meta)."""
    cfg = configs.get_config(arch)
    if shape.kind == "train":
        cfg = cfg.replace(remat=remat)
    if cfg_override:
        cfg = cfg.replace(**cfg_override)
    if cfg.moe_num_experts:
        # group-local dispatch aligned with the data-parallel shards
        import numpy as _np
        n_batch_shards = int(_np.prod(
            [mesh.shape[a] for a in ("pod", "data") if a in mesh.shape]))
        cfg = cfg.replace(moe_groups=n_batch_shards)
        if shape.kind != "train":
            # inference dispatch: no capacity slack (drops are rare and
            # lossless-irrelevant at prefill; saves 20% dispatch memory)
            cfg = cfg.replace(moe_capacity_factor=1.0)
    api = registry.get_model(cfg)
    mode = _weight_mode(cfg, shape.kind, mesh)

    pspecs = shd.param_specs(cfg, registry.param_specs(cfg), mesh,
                             weight_mode=mode)
    p_sh = shd.to_named(pspecs, mesh)
    param_structs = registry.param_specs(cfg)
    ba = shd.batch_axes(mesh)

    ins = configs.input_specs(cfg, shape)

    if shape.kind == "train":
        from ..training.train_loop import make_train_step
        from ..training.optimizer import adamw_init
        # microbatch accumulation keeps big-model activations inside HBM
        nparams = registry.param_count(cfg)
        accum = 8 if nparams > 60e9 else (4 if nparams > 25e9 else
                                          (2 if nparams > 5e9 else 1))
        if cfg.moe_num_experts:
            accum = max(accum, 2)  # halve the per-micro dispatch buffers
        if accum_override is not None:
            accum = accum_override
        if accum > 1:
            ins["batch"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    (accum, s.shape[0] // accum) + s.shape[1:], s.dtype),
                ins["batch"])
        # >100B models: bf16 second moment + bf16 grad accumulation to fit
        # the AdamW state inside 16 GB/chip (DESIGN.md §4)
        huge = nparams > 100e9
        accum_dtype = jnp.bfloat16 if huge else jnp.float32
        mv_dtype = jnp.bfloat16 if huge else jnp.float32
        _, train_step = make_train_step(cfg, accum=accum,
                                        accum_dtype=accum_dtype)
        opt_structs = jax.eval_shape(
            lambda p: adamw_init(p, m_dtype=mv_dtype, v_dtype=mv_dtype),
            param_structs)
        opt_specs = jax.tree.map(
            lambda s: s if isinstance(s, P) else None, {
                "m": pspecs, "v": pspecs})
        o_sh = {"step": NamedSharding(mesh, P()),
                "m": shd.to_named(pspecs, mesh),
                "v": shd.to_named(pspecs, mesh)}
        from ..training.optimizer import AdamWState
        o_sh = AdamWState(step=NamedSharding(mesh, P()),
                          m=shd.to_named(pspecs, mesh),
                          v=shd.to_named(pspecs, mesh))
        if accum > 1:
            def _mb_spec(path, leaf):
                dims = [None] * len(leaf.shape)
                dims[1] = ba
                if shd._leaf_name(path) == "enc_emb" and \
                        leaf.shape[2] % mesh.shape["model"] == 0:
                    dims[2] = "model"
                return P(*dims)
            b_specs = jax.tree_util.tree_map_with_path(_mb_spec, ins["batch"])
        else:
            b_specs = shd.data_specs(cfg, ins["batch"], mesh)
        b_sh = shd.to_named(b_specs, mesh)

        def fn(params, opt, batch):
            with shd.activation_sharding(ba, "model"):
                return train_step(params, opt, batch)

        args = (param_structs, opt_structs, ins["batch"])
        in_sh = (p_sh, o_sh, b_sh)
        out_sh = ({"loss": NamedSharding(mesh, P()),
                   "grad_norm": NamedSharding(mesh, P())}, p_sh, o_sh)
        donate = (0, 1)
    elif shape.kind == "prefill":
        b_specs = shd.data_specs(cfg, ins["batch"], mesh)
        b_sh = shd.to_named(b_specs, mesh)
        max_len = shape.seq_len
        cache_structs = jax.eval_shape(
            lambda: _cache_for(api, cfg, shape))
        c_sh = shd.to_named(shd.cache_specs(cfg, cache_structs, mesh), mesh)

        def fn(params, batch):
            with shd.activation_sharding(ba, "model"):
                logits, cache = api.prefill(params, batch, max_len)
            return logits, cache

        args = (param_structs, ins["batch"])
        in_sh = (p_sh, b_sh)
        out_sh = (NamedSharding(mesh, P()), c_sh)
        donate = ()
    else:  # decode
        cache_structs = ins["cache"]
        c_sh = shd.to_named(shd.cache_specs(cfg, cache_structs, mesh), mesh)
        t_sh = shd.to_named(shd.token_specs(ins["tokens"], mesh), mesh)

        if not spec_decode:
            def fn(params, cache, tokens):
                with shd.activation_sharding(ba, "model"):
                    return api.decode_step(params, cache, tokens)
            args = (param_structs, cache_structs, ins["tokens"])
            in_sh = (p_sh, c_sh, t_sh)
            out_sh = (NamedSharding(mesh, P()), c_sh)
            donate = (1,)
        else:
            from ..core.spec_decode import make_spec_step
            dcfg = configs.get_draft_config(arch)
            dapi = registry.get_model(dcfg)
            d_structs = registry.param_specs(dcfg)
            dp_sh = shd.to_named(
                shd.param_specs(dcfg, d_structs, mesh, weight_mode="tp"), mesh)
            B = shape.global_batch
            dc_structs = jax.eval_shape(lambda: dapi.init_cache(B, shape.seq_len))
            dc_sh = shd.to_named(shd.cache_specs(dcfg, dc_structs, mesh), mesh)
            gamma = 3
            step = make_spec_step(registry.get_model(cfg), dapi)
            key = jax.ShapeDtypeStruct((2,), jnp.uint32)
            last = jax.ShapeDtypeStruct((B,), jnp.int32)

            def fn(k, tp, dp, tc, dc, lt):
                return step(k, tp, dp, tc, dc, lt, gamma=gamma)
            args = (key, param_structs, d_structs, cache_structs,
                    dc_structs, last)
            in_sh = (NamedSharding(mesh, P()), p_sh, dp_sh, c_sh, dc_sh,
                     shd.to_named(shd.token_specs(last, mesh), mesh))
            out_sh = None
            donate = (3, 4)

    meta = {"arch": arch, "shape": shape.name, "kind": shape.kind,
            "weight_mode": mode, "params": registry.param_count(cfg)}
    return fn, args, in_sh, out_sh, donate, meta


def _cache_for(api, cfg, shape):
    B = shape.global_batch
    if cfg.family == "encdec":
        # prefill encodes shape.seq_len frames -> cross-KV length = seq_len;
        # decode shapes use the fixed 30 s window (cfg.enc_context)
        enc_len = shape.seq_len if shape.kind == "prefill" else cfg.enc_context
        # decoder prompt is seq_len // 4 at prefill (DESIGN.md §6)
        dec_len = max(shape.seq_len // 4, 1) if shape.kind == "prefill" \
            else shape.seq_len
        return api.init_cache(B, dec_len, enc_len=enc_len)
    return api.init_cache(B, shape.seq_len)


def run_cell(arch: str, shape: ShapeConfig, mesh, *, spec_decode=False,
             verbose=True, cfg_override=None, accum_override=None):
    fn, args, in_sh, out_sh, donate, meta = build_cell(
        arch, shape, mesh, spec_decode=spec_decode, cfg_override=cfg_override,
        accum_override=accum_override)
    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    hlo = compiled.as_text()
    coll_bytes, coll_counts = collective_bytes(hlo)
    upcast_artifact = _cpu_upcast_artifact(hlo)

    flops = float(ca.get("flops", 0.0))
    bytes_accessed = float(ca.get("bytes accessed", 0.0))
    n_chips = mesh.devices.size

    # memory_analysis is per-device under SPMD (verified empirically)
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    peak_tpu = peak - upcast_artifact  # artifact absent on the TPU backend
    arg_bytes = ma.argument_size_in_bytes

    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    nparams = (registry.active_param_count(configs.get_config(arch))
               if shape.kind != "train" else meta["params"])
    model_flops = (6 if shape.kind == "train" else 2) * nparams * tokens

    res = {
        **meta,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "chips": n_chips,
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "collective_bytes_per_device": coll_bytes,
        "collectives": coll_counts,
        "peak_bytes_per_device": peak,
        "cpu_upcast_artifact_bytes": upcast_artifact,
        "peak_bytes_tpu_adjusted": peak_tpu,
        "argument_bytes_per_device": arg_bytes,
        "temp_bytes_per_device": ma.temp_size_in_bytes,
        "ideal_memory_s": arg_bytes / HBM_BW,
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": bytes_accessed / HBM_BW,
        "collective_s": coll_bytes / LINK_BW,
        "model_flops_global": model_flops,
        "useful_flops_ratio": model_flops / max(flops * n_chips, 1.0),
        "fits_hbm": peak_tpu < HBM_BYTES,
    }
    terms = {"compute": res["compute_s"], "memory": res["memory_s"],
             "collective": res["collective_s"]}
    res["bottleneck"] = max(terms, key=terms.get)
    res["step_time_s"] = max(terms.values())
    res["roofline_fraction"] = (
        model_flops / n_chips / PEAK_FLOPS) / max(res["step_time_s"], 1e-30)
    if verbose:
        print(f"[{meta['arch']} x {shape.name} @ {res['mesh']} "
              f"mode={meta['weight_mode']}]")
        print(f"  memory_analysis: peak/device = {peak/1e9:.2f} GB raw, "
              f"{peak_tpu/1e9:.2f} GB tpu-adjusted "
              f"(fits 16GB: {res['fits_hbm']})")
        print(f"  cost_analysis: flops/dev={flops:.3e} bytes/dev="
              f"{bytes_accessed:.3e}")
        print(f"  collectives: {coll_counts} bytes/dev={coll_bytes:.3e}")
        print(f"  roofline terms (s): compute={res['compute_s']:.4f} "
              f"memory={res['memory_s']:.4f} "
              f"collective={res['collective_s']:.4f} "
              f"-> bottleneck={res['bottleneck']}")
        print(f"  MODEL_FLOPS/HLO_FLOPS={res['useful_flops_ratio']:.3f} "
              f"roofline_fraction={res['roofline_fraction']:.3f}")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--spec", action="store_true",
                    help="lower the speculative (draft+verify) serve step")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    # reuse compilations across dry-run invocations (same lowering -> hit)
    enable_compile_cache()

    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(multi_pod=False),
                  make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    cells = []
    if args.all:
        for arch in configs.ASSIGNED_ARCHS:
            for shape in shapes_for(configs.get_config(arch)):
                cells.append((arch, shape))
    else:
        cfg = configs.get_config(args.arch)
        if args.shape:
            cells = [(args.arch, configs.get_shape(args.shape))]
        else:
            cells = [(args.arch, s) for s in shapes_for(cfg)]

    results = []
    failures = []
    for mesh in meshes:
        for arch, shape in cells:
            try:
                results.append(run_cell(arch, shape, mesh,
                                        spec_decode=args.spec))
            except Exception as e:  # noqa: BLE001
                failures.append((arch, shape.name, str(e)[:500]))
                print(f"FAILED {arch} x {shape.name}: {str(e)[:300]}",
                      file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    print(f"\n{len(results)} cells compiled, {len(failures)} failed")
    for f_ in failures:
        print("  FAIL:", f_[0], f_[1])
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
