import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell on 512 placeholder host devices and extract the roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh pod|multipod|both]

Per cell this produces results/dryrun/<arch>__<shape>__<mesh>.json with:
  memory_analysis (proves it fits), cost_analysis, trip-count-corrected
  dot FLOPs / bytes, per-kind collective wire bytes, the three roofline
  terms, MODEL_FLOPS and the useful-compute ratio.
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import get_config, list_archs
from ..models import build_model
from ..parallel.sharding import tree_specs_to_shardings
from ..train import AdamW, make_train_step
from ..train.optimizer import Adafactor
from .cells import (SHAPES, active_param_count, batch_specs, cell_supported,
                    plan_cell)
from .hlo import parse_module
from .mesh import make_production_mesh
from .roofline import HW, roofline_terms

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _sds(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def compile_cell(arch: str, shape_name: str, multi_pod: bool,
                 extra: dict | None = None):
    """Lower + compile one cell; returns (compiled, plan, timings)."""
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = plan_cell(cfg, shape_name, mesh)
    if extra:
        for k, v in extra.items():
            if v is not None:
                setattr(plan, k, v)
    lowered, timings = _lower_cell(cfg, plan, shape_name, mesh)
    t0 = time.time()
    compiled = lowered.compile()
    timings["compile_s"] = time.time() - t0
    return compiled, plan, timings


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             extra: dict | None = None) -> dict:
    cfg = get_config(arch)
    mesh_name = "multipod" if multi_pod else "pod"
    out = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "ok": False}
    ok, reason = cell_supported(cfg, shape_name)
    if not ok:
        out.update(skipped=True, skip_reason=reason)
        return out

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(mesh.devices.shape))
    plan = plan_cell(cfg, shape_name, mesh)
    if extra:
        for k, v in extra.items():
            setattr(plan, k, v)
    out["plan"] = {"num_microbatches": plan.num_microbatches,
                   "opt_dtype": plan.opt_dtype, "optimizer": plan.optimizer,
                   "accum_dtype": plan.accum_dtype, "remat": plan.remat,
                   "profile": plan.profile,
                   "seq_parallel": plan.seq_parallel,
                   "est_bytes_per_chip": plan.est_bytes_per_chip}

    lowered, timings = _lower_cell(cfg, plan, shape_name, mesh)
    out.update(timings)
    t0 = time.time()
    compiled = lowered.compile()
    out["compile_s"] = time.time() - t0
    return _analyze(out, compiled, cfg, plan, shape_name, n_dev)


def _lower_cell(cfg, plan, shape_name: str, mesh):
    from ..parallel.sharding import PROFILES
    rules = PROFILES[plan.profile]
    model = build_model(cfg, mesh=mesh, remat=plan.remat, sp=plan.seq_parallel,
                        rules=rules)
    pspecs = model.param_pspecs(mesh)
    params_sh = tree_specs_to_shardings(pspecs, mesh)
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    sh = SHAPES[shape_name]
    t0 = time.time()

    with mesh:
        if plan.kind == "train":
            if plan.optimizer == "adafactor":
                opt = Adafactor()
            else:
                opt = AdamW(state_dtype=plan.opt_dtype)
            opt_specs = opt.state_pspecs(pspecs, params_sds)
            state_specs = {"params": pspecs, "opt": opt_specs, "step": P()}
            state_sh = tree_specs_to_shardings(state_specs, mesh)
            state_sds = {"params": params_sds,
                         "opt": jax.eval_shape(opt.init, params_sds),
                         "step": jax.ShapeDtypeStruct((), jnp.int32)}
            b_sds, b_specs = batch_specs(cfg, shape_name, mesh, rules)
            b_sh = tree_specs_to_shardings(b_specs, mesh)
            step = make_train_step(model, opt,
                                   num_microbatches=plan.num_microbatches,
                                   accum_dtype=plan.accum_dtype,
                                   param_specs=pspecs, mesh=mesh)
            fn = jax.jit(step, in_shardings=(state_sh, b_sh),
                         donate_argnums=(0,))
            lowered = fn.lower(state_sds, b_sds)
        elif plan.kind == "prefill":
            b_sds, b_specs = batch_specs(cfg, shape_name, mesh)
            b_sh = tree_specs_to_shardings(b_specs, mesh)

            def prefill(params, batch):
                kw = {"frames": batch["frames"]} if "frames" in batch else {}
                return model.forward(params, batch["tokens"], **kw)

            fn = jax.jit(prefill, in_shardings=(params_sh, b_sh))
            lowered = fn.lower(params_sds, b_sds)
        else:  # decode
            batch, seq = sh["batch"], sh["seq"]
            cache_sds = jax.eval_shape(
                lambda: model.init_cache(batch, seq))
            cache_specs = model.cache_pspecs(mesh, batch, seq)
            cache_sh = tree_specs_to_shardings(cache_specs, mesh)
            tok_sds = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
            tok_spec = P(tuple(a for a in ("pod", "data")
                               if a in mesh.axis_names), None) \
                if batch % 2 == 0 else P(None, None)
            from ..parallel.sharding import spec_for
            tok_spec = spec_for((batch, 1), ("batch", None), mesh)

            def serve(params, cache, tokens, pos):
                return model.decode_step(params, cache, tokens, pos)

            fn = jax.jit(serve, in_shardings=(
                params_sh, cache_sh, NamedSharding(mesh, tok_spec),
                NamedSharding(mesh, P())), donate_argnums=(1,))
            lowered = fn.lower(params_sds, cache_sds, tok_sds,
                               jax.ShapeDtypeStruct((), jnp.int32))
    return lowered, {"lower_s": time.time() - t0}


def _f32_upcast_bytes(txt: str, floor: int = 64 << 20) -> float:
    """Bytes of large f32 buffers that are pure upcasts of bf16 program
    values.  The CPU backend has no native bf16 dot: every bf16 matmul
    operand is converted to a materialized f32 copy (and XLA hoists those
    copies out of scan loops, f32-doubling e.g. whole KV-cache stacks).
    The TPU backend consumes bf16 directly in the MXU, so these buffers do
    not exist there.  Deduplicated by shape (conservative)."""
    import re as _re
    bf16_vals = set()
    for m in _re.finditer(r"%([\w.\-]+) = bf16\[", txt):
        bf16_vals.add(m.group(1))
    seen = set()
    total = 0.0
    for m in _re.finditer(
            r"= f32\[([0-9,]+)\][^\n]*? convert\(%([\w.\-]+)\)", txt):
        dims, src = m.groups()
        if src not in bf16_vals or dims in seen:
            continue
        n = 1
        for d in dims.split(","):
            n *= int(d)
        if n * 4 >= floor:
            seen.add(dims)
            total += n * 4
    return total


def _analyze(out: dict, compiled, cfg, plan, shape_name: str, n_dev: int) -> dict:
    sh = SHAPES[shape_name]
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        mem = {"argument_bytes": getattr(ma, "argument_size_in_bytes", None),
               "output_bytes": getattr(ma, "output_size_in_bytes", None),
               "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
               "alias_bytes": getattr(ma, "alias_size_in_bytes", None)}
        live = ((mem["argument_bytes"] or 0) + (mem["output_bytes"] or 0)
                + (mem["temp_bytes"] or 0) - (mem["alias_bytes"] or 0))
        mem["peak_bytes_per_device"] = live
        mem["fits_16GB"] = bool(live < 16e9)
        upcast = _f32_upcast_bytes(compiled.as_text())
        mem["cpu_f32_upcast_bytes"] = upcast
        mem["peak_bytes_tpu_estimate"] = live - upcast
        mem["fits_16GB_tpu_estimate"] = bool(live - upcast < 16e9)
    out["memory"] = mem
    out["cost_analysis"] = {"flops_raw": float(ca.get("flops", 0.0)),
                            "bytes_raw": float(ca.get("bytes accessed", 0.0))}

    t0 = time.time()
    hlo = parse_module(compiled.as_text())
    out["hlo_parse_s"] = time.time() - t0
    out["hlo"] = hlo.summary()

    from ..models.common import ParamDef
    model = build_model(cfg)
    params_total = sum(
        int(np.prod(d.shape)) for d in jax.tree.leaves(
            model.defs(), is_leaf=lambda x: isinstance(x, ParamDef)))
    n_active = active_param_count(cfg)
    tokens = sh["batch"] * (sh["seq"] if plan.kind != "decode" else 1)
    if plan.kind == "train":
        model_flops = 6.0 * n_active * tokens
    else:
        model_flops = 2.0 * n_active * tokens
    out["params_total"] = params_total
    out["params_active"] = n_active
    out["model_flops"] = model_flops
    out["roofline"] = roofline_terms(
        flops_per_dev=hlo.dot_flops, bytes_per_dev=hlo.dot_bytes,
        wire_bytes_per_dev=hlo.total_wire_bytes, n_dev=n_dev,
        model_flops=model_flops)
    # TPU-deployment terms: attention through the Pallas flash kernel
    # (scores/probs stay in VMEM; only Q/K/V/O stream from HBM) and bf16
    # collectives (the CPU backend upcasts them to f32)
    out["roofline_flash"] = roofline_terms(
        flops_per_dev=hlo.dot_flops, bytes_per_dev=hlo.dot_bytes_flash,
        wire_bytes_per_dev=hlo.total_wire_bytes_bf16, n_dev=n_dev,
        model_flops=model_flops)
    out["ok"] = True
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--seq-parallel", dest="sp", default=None,
                    choices=["on", "off"])
    ap.add_argument("--profile", default=None, choices=["tp2d", "fsdp", "fsdp_ep"])
    ap.add_argument("--remat", default=None, choices=["none", "full", "2level"])
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    extra = {}
    if args.microbatches is not None:
        extra["num_microbatches"] = args.microbatches
    if args.sp is not None:
        extra["seq_parallel"] = args.sp == "on"
    if args.profile is not None:
        extra["profile"] = args.profile
    if args.remat is not None:
        extra["remat"] = args.remat

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "multipod" if mp else "pod"
                tag = f"__{args.tag}" if args.tag else ""
                path = os.path.join(
                    args.out, f"{arch}__{shape}__{mesh_name}{tag}.json")
                t0 = time.time()
                try:
                    res = run_cell(arch, shape, mp, extra or None)
                except Exception as e:  # noqa: BLE001
                    res = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "ok": False, "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                    failures += 1
                with open(path, "w") as f:
                    json.dump(res, f, indent=1, default=float)
                status = ("SKIP" if res.get("skipped")
                          else "OK" if res.get("ok") else "FAIL")
                msg = res.get("error", "")
                if res.get("ok"):
                    r = res["roofline"]
                    msg = (f"dom={r['dominant']} comp={r['compute_s']:.4f}s "
                           f"mem={r['memory_s']:.4f}s coll={r['collective_s']:.4f}s "
                           f"fit={res['memory'].get('fits_16GB')}")
                print(f"[{status}] {arch} {shape} {mesh_name} "
                      f"({time.time()-t0:.0f}s) {msg}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
