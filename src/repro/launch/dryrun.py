import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape x mesh) cell: build the step
function (train_step / prefill_step / serve_step), attach the cell's
shardings to ShapeDtypeStruct stand-ins, ``jax.jit(...).lower()``,
``.compile()``, and record ``memory_analysis()`` + ``cost_analysis()``
plus the HLO collective inventory into results/dryrun/<cell>.json.

The 512 placeholder host devices exist ONLY here (the two lines above
run before any other import, since jax locks the device count on first
init). Smoke tests and benches see 1 device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch all --shape all --mesh both --out results/dryrun
"""

import argparse
import json
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..configs import ARCHS, SHAPES, cells_for, get_config, input_specs, \
    shape_applicable
from ..distributed.optimizer import Optimizer, OptimizerConfig
from ..models.config import ModelConfig
from ..models.registry import abstract_params, get_api
from ..models.steps import make_prefill_step, make_serve_step, make_train_step
from . import cell_shardings as cs
from .analysis import count_collective_ops, parse_collective_bytes, \
    summarize_cell
from .mesh import make_production_mesh


def _cell_id(arch: str, shape: str, multi_pod: bool) -> str:
    return f"{arch}__{shape}__{'multipod' if multi_pod else 'pod'}"


def lower_cell(cfg: ModelConfig, shape_name: str, mesh, *,
               donate: bool = True, attn_impl: str = "auto",
               remat: bool = True, param_mode: Optional[str] = None,
               batch_mode: str = "default",
               rules_mode: Optional[str] = None):
    """Build + lower one cell. Returns (lowered, meta).

    ``param_mode``: train | serve | replicated | serve-2d (see
    cell_shardings.params_shardings_for). ``batch_mode``: default |
    dp-all (batch over the model axis too; activation constraints switch
    to the pure-DP rule set)."""
    from ..distributed.sharding import auto_axes, logical_mode
    mesh = auto_axes(mesh)
    shape = SHAPES[shape_name]
    specs = input_specs(cfg, shape)
    aparams = abstract_params(cfg)
    meta: Dict = {"kind": shape.kind,
                  "variant_knobs": {"param_mode": param_mode,
                                    "batch_mode": batch_mode,
                                    "rules_mode": rules_mode,
                                    "remat": remat}}
    rules = rules_mode or ("dp-all" if batch_mode == "dp-all" else "default")

    if shape.kind == "train":
        p_shard, policy = cs.params_shardings_for(
            cfg, mesh, aparams,
            mode=param_mode or "train")
        opt = Optimizer(OptimizerConfig())
        aopt = jax.eval_shape(opt.init, aparams)
        o_shard = opt.state_shardings(aparams, mesh)
        b_shard = cs.train_batch_shardings(mesh, specs, mode=batch_mode)
        step = make_train_step(cfg, opt, remat=remat, attn_impl=attn_impl)
        args = (cs.attach(aparams, p_shard),
                cs.attach(aopt, o_shard),
                cs.attach(specs, b_shard))
        jitted = jax.jit(step, donate_argnums=(0, 1) if donate else ())
        with mesh, logical_mode(rules):
            lowered = jitted.lower(*args)
        meta["param_policy"] = policy
        meta["n_tokens"] = shape.global_batch * shape.seq_len
        return lowered, meta

    p_shard, policy = cs.params_shardings_for(
        cfg, mesh, aparams, mode=param_mode or "serve")
    meta["param_policy"] = policy

    if shape.kind == "prefill":
        b_shard = cs.train_batch_shardings(mesh, specs, mode=batch_mode)
        step = make_prefill_step(cfg, max_len=shape.seq_len,
                                 attn_impl=attn_impl)
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        args = (cs.attach(aparams, p_shard),
                cs.attach(specs, b_shard),
                rng)
        with mesh, logical_mode(rules):
            lowered = jax.jit(step).lower(*args)
        meta["n_tokens"] = shape.global_batch * shape.seq_len
        return lowered, meta

    # decode
    c_shard = cs.cache_shardings(cfg, mesh, specs["cache"])
    step = make_serve_step(cfg, attn_impl=attn_impl)
    args = (cs.attach(aparams, p_shard),
            cs.attach(specs["cache"], c_shard),
            cs.attach(specs["tokens"], cs.token_sharding(
                mesh, shape.global_batch)),
            specs["pos"],
            specs["rng"])
    jitted = jax.jit(step, donate_argnums=(1,) if donate else ())
    with mesh, logical_mode(rules):
        lowered = jitted.lower(*args)
    meta["n_tokens"] = shape.global_batch  # one new token per sequence
    return lowered, meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str, force: bool = False,
             save_hlo: bool = False, variant: str = "",
             **lower_kw) -> Dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    cell = _cell_id(arch, shape_name, multi_pod) + (
        f"__{variant}" if variant else "")
    path = os.path.join(out_dir, cell + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    record: Dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "variant": variant,
    }
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        record.update({"status": "skipped", "reason": reason})
        _save(path, record)
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    try:
        with mesh:
            lowered, meta = lower_cell(cfg, shape_name, mesh, **lower_kw)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            cost = compiled.cost_analysis() or {}
            try:
                mem = compiled.memory_analysis()
                mem_info = {
                    k: int(getattr(mem, k))
                    for k in ("argument_size_in_bytes",
                              "output_size_in_bytes",
                              "temp_size_in_bytes",
                              "generated_code_size_in_bytes",
                              "alias_size_in_bytes")
                    if hasattr(mem, k)
                } if mem is not None else {}
            except Exception as e:  # CPU backend may not implement it
                mem_info = {"error": str(e)}

            hlo = compiled.as_text()
            coll_bytes = parse_collective_bytes(hlo)
            coll_ops = count_collective_ops(hlo)
            if save_hlo:
                with open(os.path.join(out_dir, cell + ".hlo.txt"), "w") as f:
                    f.write(hlo)

        io_bytes = float(mem_info.get("argument_size_in_bytes", 0)
                         + mem_info.get("output_size_in_bytes", 0)) \
            if "error" not in mem_info else 0.0
        summary = summarize_cell(cfg, meta["kind"], meta["n_tokens"],
                                 n_chips, cost, coll_bytes,
                                 io_bytes=io_bytes)
        record.update({
            "status": "ok",
            "n_chips": int(n_chips),
            "param_policy": meta.get("param_policy"),
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "cost_analysis": {k: float(v) for k, v in cost.items()
                              if isinstance(v, (int, float))},
            "memory_analysis": mem_info,
            "collective_bytes": coll_bytes,
            "collective_ops": coll_ops,
            "roofline": summary,
        })
    except Exception as e:
        record.update({
            "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        })
    _save(path, record)
    return record


def _save(path: str, record: Dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help=f"one of {ARCHS} or 'all' or comma list")
    ap.add_argument("--shape", default="all",
                    help=f"one of {list(SHAPES)} or 'all' or comma list")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args(argv)

    archs = ARCHS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                rec = run_cell(arch, shape, multi_pod=multi,
                               out_dir=args.out, force=args.force,
                               save_hlo=args.save_hlo)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" bottleneck={r['bottleneck']}"
                             f" t>={r['step_time_lower_bound_s']:.4f}s"
                             f" compile={rec['compile_s']:.0f}s")
                elif status == "error":
                    failures += 1
                    extra = " " + rec["error"][:120]
                print(f"[{status:7s}] {arch} x {shape} x "
                      f"{'2x16x16' if multi else '16x16'}{extra}",
                      flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
