import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
# on the production meshes with ShapeDtypeStruct inputs (zero allocation),
# then extract memory_analysis / cost_analysis / HLO collectives for the
# roofline report.
#
# Usage:
#   python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
#   python -m repro.launch.dryrun --all --multi-pod both --out dryrun.jsonl

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as PS  # noqa: E402

from repro.analysis import roofline  # noqa: E402
from repro.analysis.costmodel import MeshSpec  # noqa: E402
from repro.configs import ARCHS, LM_SHAPES, get_arch, shape_applicable  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_spec  # noqa: E402
from repro.models import spec as pspec  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.model_zoo import build_model  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.parallel import sharding as shd  # noqa: E402


def batch_shardings(mesh, specs, batch_axes=("pod", "data")):
    axes = tuple(a for a in batch_axes if a in mesh.shape)
    bspec = axes if len(axes) > 1 else (axes[0] if axes else None)

    def one(sds):
        b = sds.shape[0]
        n = 1
        for a in (axes if isinstance(bspec, tuple) else
                  ((bspec,) if bspec else ())):
            n *= mesh.shape[a]
        first = bspec if (n > 1 and b % n == 0) else None
        return NamedSharding(mesh, PS(first, *([None] * (len(sds.shape) - 1))))
    return jax.tree_util.tree_map(one, specs)


_STATE_AXES = {
    # the head-major cache resolves kv heads before seq: kv heads shard
    # over model where their count divides it (each device attends over
    # whole heads), else the cache sequence dim does ("seq" rule).  None
    # of the assigned archs can shard kv heads over tp=16, so they shard
    # seq: a replicated 32k cache is the decode memory bottleneck (see
    # EXPERIMENTS.md #Perf).
    "cache_k": ("layers", "batch", "kv_heads", "seq", None),
    "cache_v": ("layers", "batch", "kv_heads", "seq", None),
    "pos": (),
    "x_prev": ("layers", "batch", None),
    "cm_prev": ("layers", "batch", None),
    "wkv": ("layers", "batch", "heads", None, None),
    "conv_tail": ("layers", "batch", None, None),
    "ssm_h": ("layers", "batch", None, "state"),
}


def decode_state_shardings(state, mesh):
    out = {}
    for name, val in state._asdict().items():
        if val is None:
            out[name] = None
            continue
        axes = _STATE_AXES[name][:len(val.shape)]
        out[name] = NamedSharding(mesh, shd.spec_for(val.shape, axes, mesh))
    return type(state)(**out)


def opt_shardings(spec_tree, mesh, moment_dtype: str, rules=None):
    p_sh = shd.tree_shardings(spec_tree, mesh, rules)

    def moment(psh, p):
        if moment_dtype != "int8":
            return psh
        scale_axes = tuple(p.axes[:-1]) + (None,) if p.axes else ()
        scale_shape = tuple(p.shape[:-1]) + (1,) if p.shape else ()
        if not p.shape:
            return adamw.QMoment(psh, NamedSharding(mesh, PS()))
        return adamw.QMoment(
            NamedSharding(mesh, shd.spec_for(p.shape, p.axes, mesh, rules)),
            NamedSharding(mesh, shd.spec_for(scale_shape, scale_axes, mesh,
                                             rules)))

    m = jax.tree_util.tree_map(moment, p_sh, pspec.tree_map_specs(
        lambda p: p, spec_tree), is_leaf=lambda x: isinstance(x, NamedSharding))
    return adamw.AdamWState(NamedSharding(mesh, PS()), m, m)


def abstract_opt_state(spec_tree, moment_dtype: str):
    def mom(p):
        if moment_dtype == "int8":
            scale_shape = tuple(p.shape[:-1]) + (1,) if p.shape else ()
            return adamw.QMoment(
                jax.ShapeDtypeStruct(p.shape, jnp.int8),
                jax.ShapeDtypeStruct(scale_shape, jnp.float32))
        return jax.ShapeDtypeStruct(p.shape, jnp.float32)
    m = pspec.tree_map_specs(mom, spec_tree)
    return adamw.AdamWState(jax.ShapeDtypeStruct((), jnp.int32), m, m)


# ---------------------------------------------------------------------------
# Optimized variants (the #Perf hillclimbs; see EXPERIMENTS.md)
# ---------------------------------------------------------------------------

def _variants():
    from repro.configs.base import BF16_EXEC
    from repro.parallel.sharding import PURE_DP_RULES, ZERO1_OPT_RULES
    return {
        # glm4 decode: FxP8 KV cache (+ the already-default seq-sharded
        # cache) — the paper's quantization applied to the decode memory
        # bottleneck.
        "kv8": dict(arch_overrides=dict(kv_cache_bits=8)),
        # arctic train: fuse dense-residual FFN into the MoE psum + FxP8
        # FSDP weight-gather transport.
        "moefuse": dict(arch_overrides=dict(
            fuse_moe_ffn_ar=True,
            exec_policy=dataclasses.replace(BF16_EXEC,
                                            fsdp_int8_gather=True))),
        # granite train: pure-DP profile (batch over all 256/512 chips,
        # weights replicated, ZeRO-1 int8 moments over the mesh).
        # paper-faithful FxP8 execution: every projection on the MXU int8
        # path (the production mapping of the 5-stage CORDIC MAC).
        "fxp8": dict(arch_overrides=dict(
            exec_policy=dataclasses.replace(BF16_EXEC, matmul="fxp8"))),
        "puredp": dict(arch_overrides=dict(
            exec_policy=dataclasses.replace(BF16_EXEC, moe_pure_dp=True)),
            param_rules=PURE_DP_RULES, opt_rules=ZERO1_OPT_RULES,
            batch_axes=("pod", "data", "model")),
    }


def build_step(arch_name: str, shape_name: str, mesh,
               moment_dtype: str = None, arch_overrides: dict = None,
               param_rules=None, opt_rules=None, batch_axes=None):
    """Returns (jitted fn, abstract args tuple) for one cell."""
    cfg = get_arch(arch_name)
    if arch_overrides:
        cfg = cfg.scaled(**arch_overrides)
    shape = LM_SHAPES[shape_name]
    model = build_model(cfg)
    spec_tree = model.params_spec()
    if moment_dtype is None:
        # quantization co-design default: int8 Adam moments everywhere
        # (arctic's 469B expert slab requires it; the others gain headroom)
        moment_dtype = "int8"
    ocfg = adamw.AdamWConfig(moment_dtype=moment_dtype)

    params_abs = model.abstract_params()
    p_sh = shd.tree_shardings(spec_tree, mesh, param_rules)
    batch_axes = batch_axes or ("pod", "data")
    dp = 1
    for a in batch_axes:
        if a in mesh.shape:
            dp *= mesh.shape[a]

    if shape.kind == "train":
        batch_abs = model.input_specs(shape.global_batch, shape.seq_len,
                                      "train")
        opt_abs = abstract_opt_state(spec_tree, moment_dtype)
        # Production memory recipe (CAESAR quantization co-design, see
        # DESIGN.md §Memory): microbatch so each device sees <= 8192 tokens
        # per backward pass; accumulate grads in bf16; int8 Adam moments.
        tokens_dev = (shape.global_batch // dp
                      if shape.global_batch % dp == 0
                      else shape.global_batch) * shape.seq_len
        accum = max(1, tokens_dev // 8192)
        while shape.global_batch % accum or \
                (shape.global_batch // accum) % min(dp, shape.global_batch):
            accum //= 2
        accum = max(accum, 1)

        def train_step(params, opt_state, batch):
            mb = shape.global_batch // accum

            def micro(i, carry):
                gsum, lsum = carry
                mbatch = jax.tree_util.tree_map(
                    lambda x: jax.lax.dynamic_slice_in_dim(
                        x, i * mb, mb, axis=0), batch)
                (l, _), g = jax.value_and_grad(
                    lambda p: model.loss(p, mbatch), has_aux=True)(params)
                gsum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(a.dtype), gsum, g)
                return gsum, lsum + l

            if accum > 1:
                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, p.dtype), params)
                grads, lsum = jax.lax.fori_loop(
                    0, accum, micro, (zeros, jnp.float32(0.0)))
                grads = jax.tree_util.tree_map(
                    lambda g: g / accum, grads)
                loss = lsum / accum
            else:
                (loss, _), grads = jax.value_and_grad(
                    lambda p: model.loss(p, batch), has_aux=True)(params)
            new_p, new_o, om = adamw.update(ocfg, grads, opt_state, params)
            return new_p, new_o, {"loss": loss, **om}

        fn = jax.jit(
            train_step,
            in_shardings=(p_sh,
                          opt_shardings(spec_tree, mesh, moment_dtype,
                                        opt_rules or param_rules),
                          batch_shardings(mesh, batch_abs, batch_axes)),
            donate_argnums=(0, 1))
        return fn, (params_abs, opt_abs, batch_abs)

    if shape.kind == "prefill":
        batch_abs = model.input_specs(shape.global_batch, shape.seq_len,
                                      "prefill")

        def prefill_step(params, batch):
            return model.prefill(params, batch)

        fn = jax.jit(prefill_step,
                     in_shardings=(p_sh, batch_shardings(mesh, batch_abs,
                                                         batch_axes)))
        return fn, (params_abs, batch_abs)

    # decode
    batch_abs = model.input_specs(shape.global_batch, shape.seq_len, "decode")
    state_abs = model.init_decode_state(shape.global_batch, shape.seq_len,
                                        abstract=True)
    st_sh = decode_state_shardings(state_abs, mesh)

    def serve_step(params, state, batch):
        return model.decode_step(params, state, batch)

    fn = jax.jit(serve_step,
                 in_shardings=(p_sh, st_sh,
                               batch_shardings(mesh, batch_abs, batch_axes)),
                 donate_argnums=(1,))
    return fn, (params_abs, state_abs, batch_abs)


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             with_hlo: bool = True, variant: str = None) -> dict:
    cfg = get_arch(arch_name)
    vkw = dict(_variants()[variant]) if variant else {}
    arch_overrides = vkw.pop("arch_overrides", None)
    if arch_overrides:
        cfg = cfg.scaled(**arch_overrides)
    shape = LM_SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    if not ok:
        return {"arch": arch_name, "shape": shape_name, "mesh": mesh_tag,
                "status": "skipped", "reason": reason}
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules_ctx = (shd.use_rules(vkw["param_rules"]) if
                 vkw.get("param_rules") else None)
    try:
        with jax.set_mesh(mesh):
            import contextlib
            with (rules_ctx or contextlib.nullcontext()):
                fn, args = build_step(arch_name, shape_name, mesh,
                                      arch_overrides=arch_overrides, **vkw)
                lowered = fn.lower(*args)
                compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo_text = compiled.as_text() if with_hlo else None
    except Exception as e:
        return {"arch": arch_name, "shape": shape_name, "mesh": mesh_tag,
                "status": "error", "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2000:]}
    # per-device weight shard shapes (for the CPU f32-upcast correction)
    model = build_model(cfg)  # cfg includes variant overrides
    spec_tree = model.params_spec()
    shard_shapes = []
    for p in jax.tree_util.tree_leaves(
            pspec.tree_map_specs(lambda q: q, spec_tree),
            is_leaf=pspec.is_spec):
        if not isinstance(p, pspec.P) or len(p.shape) < 2:
            continue
        ps = shd.spec_for(p.shape, p.axes, mesh)
        shp = list(p.shape)
        for i, entry in enumerate(ps):
            if entry is None:
                continue
            axes_ = entry if isinstance(entry, tuple) else (entry,)
            n = 1
            for a in axes_:
                n *= mesh.shape[a]
            shp[i] //= n
        shard_shapes.append(tuple(shp))
    row = roofline.analyze(cfg, shape, mesh_spec(mesh), mem, cost, hlo_text,
                           param_shard_shapes=shard_shapes)
    rec = row.as_dict()
    rec.update({"status": "ok", "compile_s": round(time.time() - t0, 1),
                "variant": variant or "baseline"})
    rec.pop("note", None)
    # memory_analysis detail
    try:
        rec["mem_args_GB"] = mem.argument_size_in_bytes / 2 ** 30
        rec["mem_temp_GB"] = mem.temp_size_in_bytes / 2 ** 30
        rec["mem_out_GB"] = mem.output_size_in_bytes / 2 ** 30
    except AttributeError:
        pass
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["off", "on", "both"],
                    default="off")
    ap.add_argument("--no-hlo", action="store_true",
                    help="skip HLO text extraction (faster)")
    ap.add_argument("--out", default=None, help="append JSONL here")
    ap.add_argument("--variant", default=None,
                    help="optimized variant: kv8 | moefuse | puredp")
    args = ap.parse_args(argv)

    cells = []
    archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = (list(LM_SHAPES) if (args.all or not args.shape)
              else [args.shape])
    pods = {"off": [False], "on": [True], "both": [False, True]}[
        args.multi_pod]
    for mp in pods:
        for a in archs:
            for s in shapes:
                cells.append((a, s, mp))

    out_f = open(args.out, "a") if args.out else None
    n_ok = n_err = n_skip = 0
    for a, s, mp in cells:
        rec = run_cell(a, s, mp, with_hlo=not args.no_hlo,
                       variant=args.variant)
        status = rec["status"]
        n_ok += status == "ok"
        n_err += status == "error"
        n_skip += status == "skipped"
        line = json.dumps(rec, default=float)
        print(line, flush=True)
        if out_f:
            out_f.write(line + "\n")
            out_f.flush()
    print(f"# done: {n_ok} ok, {n_skip} skipped, {n_err} errors",
          file=sys.stderr)
    if out_f:
        out_f.close()
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
