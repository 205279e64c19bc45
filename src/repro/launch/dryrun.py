"""Multi-pod dry-run: .lower().compile() every (arch x input-shape x mesh)
combination on placeholder devices and record memory/cost/collective stats.

MUST set XLA_FLAGS before any other import (jax locks the device count on
first init) — hence the first two lines.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
        --shape train_4k --mesh single --out results/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import (  # noqa: E402
    ARCH_NAMES,
    get_config,
    input_specs,
    shape_applicable,
)
from repro.configs.base import INPUT_SHAPES  # noqa: E402
from repro.launch.hlo_cost import analyze as hlo_analyze  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.roofline import (  # noqa: E402
    model_flops,
    roofline_terms,
)
from repro.models.steps import (  # noqa: E402
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
from repro.models.transformer import abstract_lm_params, cache_spec_tree  # noqa: E402
from repro.optim import make_optimizer  # noqa: E402
from repro.sharding.partitioning import (  # noqa: E402
    replicated,
    resolve,
    tree_shardings,
)

#: the chip the production mesh models (its roofline peaks)
TARGET_KIND = "TPU v5 lite"

# jamba-398b keeps Adam moments in bf16 (HBM budget — DESIGN.md §6)
BF16_MOMENT_ARCHS = {"jamba-1.5-large-398b", "mixtral-8x22b"}


def _batch_shardings(mesh, specs):
    out = {}
    for k, v in specs.items():
        if k == "pos":
            out[k] = replicated(mesh)
        else:
            out[k] = NamedSharding(
                mesh, resolve(("batch",) + (None,) * (v.ndim - 1), v.shape, mesh)
            )
    return out


def build_case(arch, shape_name, mesh, variant="baseline"):
    import dataclasses as _dc

    from repro.models.moe import set_moe_dispatch_groups
    from repro.sharding.partitioning import rules_for_mesh

    cfg = get_config(arch)
    rules = None
    set_moe_dispatch_groups(1)
    if variant in ("moe_local", "moe_local_dots"):
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        groups = sizes.get("data", 1) * sizes.get("pod", 1)
        set_moe_dispatch_groups(groups)
        rules = rules_for_mesh(mesh, "moe_local")
        if variant == "moe_local_dots":
            cfg = _dc.replace(cfg, remat_policy="dots")
    elif variant == "decode_stationary":
        rules = rules_for_mesh(mesh, "decode_stationary")
    elif variant == "remat_dots":
        cfg = _dc.replace(cfg, remat_policy="dots")
    elif variant != "baseline":
        raise ValueError(variant)
    shape = INPUT_SHAPES[shape_name]
    specs = input_specs(cfg, shape)
    pshapes, pspecs = abstract_lm_params(cfg)
    psharding = tree_shardings(pspecs, pshapes, mesh, rules)

    if shape.kind == "train":
        moment_dtype = (
            jnp.bfloat16 if arch in BF16_MOMENT_ARCHS else jnp.float32
        )
        train_step, opt = make_train_step(cfg, "adamw", moment_dtype=moment_dtype)
        opt_shapes = jax.eval_shape(opt.init, pshapes)
        opt_sharding = type(opt_shapes)(
            step=replicated(mesh),
            m=tree_shardings(pspecs, opt_shapes.m, mesh, rules),
            v=tree_shardings(pspecs, opt_shapes.v, mesh, rules),
        )
        batch_sh = _batch_shardings(mesh, specs)
        in_sh = (psharding, opt_sharding, batch_sh)
        out_sh = (psharding, opt_sharding, replicated(mesh))
        args = (pshapes, opt_shapes, specs)
        return train_step, args, in_sh, out_sh, cfg, shape

    if shape.kind == "prefill":
        prefill_step = make_prefill_step(cfg)
        batch_sh = _batch_shardings(mesh, specs)
        cache_specs = cache_spec_tree(cfg)
        out_caches = jax.eval_shape(prefill_step, pshapes, specs)[1]
        cache_sh = tree_shardings(cache_specs, out_caches, mesh)
        logits_shape = jax.eval_shape(prefill_step, pshapes, specs)[0]
        logits_sh = NamedSharding(
            mesh, resolve(("batch", None), logits_shape.shape, mesh)
        )
        in_sh = (psharding, batch_sh)
        out_sh = (logits_sh, cache_sh)
        args = (pshapes, specs)
        return prefill_step, args, in_sh, out_sh, cfg, shape

    # decode
    serve = make_serve_step(cfg)
    caches = specs["caches"]
    cache_specs = cache_spec_tree(cfg)
    cache_sh = tree_shardings(cache_specs, caches, mesh)
    tok_sh = NamedSharding(mesh, resolve(("batch",), specs["token"].shape, mesh))
    logits_shape = (specs["token"].shape[0], cfg.vocab_size)
    logits_sh = NamedSharding(mesh, resolve(("batch", None), logits_shape, mesh))
    if "memory" in specs:
        mem_sh = NamedSharding(
            mesh, resolve(("batch", None, None), specs["memory"].shape, mesh)
        )

        def fn(params, token, pos, caches, memory):
            return serve(params, token, pos, caches, memory=memory)

        args = (pshapes, specs["token"], specs["pos"], caches, specs["memory"])
        in_sh = (psharding, tok_sh, replicated(mesh), cache_sh, mem_sh)
    else:

        def fn(params, token, pos, caches):
            return serve(params, token, pos, caches)

        args = (pshapes, specs["token"], specs["pos"], caches)
        in_sh = (psharding, tok_sh, replicated(mesh), cache_sh)
    out_sh = (logits_sh, cache_sh)
    return fn, args, in_sh, out_sh, cfg, shape


def install_activation_constraint(mesh):
    """Pin activation layouts: batch over data axes, everything else open.

    Without this GSPMD lets the embedding gather keep the TABLE sharding
    (d_model over data, batch replicated) and every block all-reduces a
    global-batch activation per layer (measured 6.4 GB/layer on phi3 —
    EXPERIMENTS.md §Perf iteration 0)."""
    from repro.models.layers import set_activation_constraint

    batch_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    import numpy as _np

    nshard = int(_np.prod([sizes[a] for a in batch_axes]))

    def constrain(x):
        axes = batch_axes if x.shape[0] % nshard == 0 else ()
        spec = P(axes if axes else None, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    set_activation_constraint(constrain)

    from repro.models.layers import set_weight_gather

    msize = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)

    def gather(w):
        # replicate all dims except the last, which stays tensor-parallel
        last = "model" if w.shape[-1] % msize == 0 else None
        spec = P(*([None] * (w.ndim - 1)), last)
        return jax.lax.with_sharding_constraint(w, NamedSharding(mesh, spec))

    set_weight_gather(gather)


def dryrun_one(arch, shape_name, multi_pod, parse_hlo=True, variant="baseline"):
    mesh = make_production_mesh(multi_pod=multi_pod)
    install_activation_constraint(mesh)
    chips = mesh.devices.size
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "variant": variant,
    }
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        record.update({"status": "skipped", "reason": reason})
        return record
    try:
        fn, args, in_sh, out_sh, cfg, shape = build_case(
            arch, shape_name, mesh, variant=variant
        )
        t0 = time.time()
        with mesh:
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
            lowered = jitted.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
        record["lower_s"] = round(t1 - t0, 2)
        record["compile_s"] = round(t2 - t1, 2)

        mem = compiled.memory_analysis()
        record["memory_analysis"] = {
            k: int(getattr(mem, k))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            )
            if hasattr(mem, k)
        }
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        record["xla_cost_flops_body_once"] = float(cost.get("flops", 0.0))
        record["xla_cost_bytes_body_once"] = float(cost.get("bytes accessed", 0.0))

        # trip-count-aware walk of the compiled module (see hlo_cost.py):
        # XLA's cost_analysis counts while bodies ONCE, so scanned layers
        # would be undercounted by their trip count.
        if parse_hlo:
            res = hlo_analyze(compiled.as_text())
        else:
            res = {"flops": 0.0, "dot_bytes": 0.0, "collective_bytes": 0.0,
                   "collective_bytes_by_kind": {}, "collective_counts_by_kind": {}}
        flops = res["flops"]
        byts = res["dot_bytes"]
        record["hlo_flops"] = flops            # per-device, trip-aware
        record["hlo_bytes"] = byts             # dot operand/output traffic proxy
        # body-once vs trip-aware divergence: when the module contains
        # scanned/while-looped layers, cost_analysis() undercounts by the
        # trip count — surface the ratio and flag it so a dryrun record
        # can never pass a body-once number off as the real FLOPs
        body_once = record["xla_cost_flops_body_once"]
        record["flops_trip_ratio"] = (
            flops / body_once if (parse_hlo and body_once) else None
        )
        record["flops_undercounted"] = bool(
            parse_hlo and body_once and flops > body_once * 1.01
        )
        record["collectives"] = {
            "bytes_by_kind": res["collective_bytes_by_kind"],
            "counts_by_kind": res["collective_counts_by_kind"],
            "total_bytes": res["collective_bytes"],
        }

        mf = model_flops(cfg, shape)
        record["model_flops"] = mf
        record["model_flops_per_chip"] = mf / chips
        # useful-compute fraction: MODEL_FLOPS / (chips x HLO flops per chip)
        record["model_flops_ratio"] = (
            mf / (chips * flops) if flops else None
        )
        record["roofline"] = roofline_terms(
            flops, byts, res["collective_bytes"], chips,
            device_kind=TARGET_KIND,
        )
        record["params"] = cfg.param_count()
        record["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record and continue the matrix
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-3000:]
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--no-hlo", action="store_true", help="skip collective parse")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "moe_local", "moe_local_dots", "decode_stationary", "remat_dots"])
    args = ap.parse_args()

    archs = list(ARCH_NAMES) if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape_name in shapes:
            for multi_pod in meshes:
                tag = f"{arch}__{shape_name}__{'multi' if multi_pod else 'single'}"
                if args.variant != "baseline":
                    tag += f"__{args.variant}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[skip existing] {tag}", flush=True)
                    continue
                print(f"[dryrun] {tag} ...", flush=True)
                rec = dryrun_one(
                    arch, shape_name, multi_pod,
                    parse_hlo=not args.no_hlo, variant=args.variant,
                )
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                extra = (
                    f" flops={rec.get('hlo_flops'):.3e} coll={rec.get('collectives', {}).get('total_bytes', 0):.3e}"
                    if status == "ok"
                    else rec.get("error", rec.get("reason", ""))
                )
                print(f"[done] {tag}: {status} {extra}", flush=True)


if __name__ == "__main__":
    main()
