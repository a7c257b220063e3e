"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against the production meshes and extract the roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun_results.json

For each cell this proves: the sharding config is coherent (no mismatched
collectives), the per-device memory fits the 16 GB v5e HBM, and it yields
HLO FLOPs / bytes / collective bytes for EXPERIMENTS.md §Roofline.
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# ^ MUST precede any jax import: jax locks the device count at first init.

import argparse
import json
import re
import sys
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs as cfgs
from repro.launch.mesh import make_production_mesh
from repro.models import (ArchConfig, ShapeConfig, abstract, decode_step,
                          init_decode_state, loss_fn, model_defs, n_params)
from repro.models.layers import abstract_params, is_def
from repro.optim import adamw
from repro.parallel import (batch_axes, data_specs, decode_state_specs,
                            param_specs, to_shardings)
from repro.train import TrainState, make_serve_step, make_train_step

# hardware constants + artifact format/digest live in dryrun_meta (the
# side-effect-free half readers import to validate persisted results)
from repro.launch.dryrun_meta import (HBM_BW, HBM_BYTES, ICI_BW, PEAK_FLOPS,
                                      WIRE_FACTOR as _WIRE_FACTOR,
                                      wrap_results)


def input_specs(arch: ArchConfig, shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for one global batch (weak-type-correct,
    shardable, zero allocation)."""
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train" or shape.kind == "prefill":
        batch = {"labels": sds((B, S), jnp.int32),
                 "mask": sds((B, S), jnp.float32)}
        if arch.frontend in ("audio", "vlm"):
            batch["embeds"] = sds((B, S, arch.d_model), jnp.bfloat16)
        else:
            batch["tokens"] = sds((B, S), jnp.int32)
        return batch
    # decode: one new token against a cache of S
    if arch.frontend in ("audio", "vlm"):
        return {"tokens": sds((B, arch.d_model), jnp.bfloat16)}
    return {"tokens": sds((B,), jnp.int32)}


def _abstract_like(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def abstract_train_state(arch: ArchConfig) -> TrainState:
    p = abstract(arch)
    zeros = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), p)
    return TrainState(params=p, opt=adamw.OptState(
        mu=zeros, nu=jax.tree.map(lambda s: s, zeros),
        count=jax.ShapeDtypeStruct((), jnp.int32)))


def abstract_decode_state(arch: ArchConfig, shape: ShapeConfig):
    st = jax.eval_shape(lambda: init_decode_state(arch, shape.global_batch,
                                                  shape.seq_len))
    return st


# --- HLO collective accounting ------------------------------------------------

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVE_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", )


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum result-shape bytes of every collective in the (post-SPMD)
    compiled module, weighted by ring wire factors. Per-device bytes."""
    out: Dict[str, float] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_str) * _WIRE_FACTOR[kind]
        out[kind] = out.get(kind, 0.0) + b
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


# --- per-cell dry run -----------------------------------------------------------

def lower_cell(arch: ArchConfig, shape: ShapeConfig, mesh, *,
               use_kernel: bool = False, unroll: bool = False):
    """Build + lower + compile one cell. Returns (compiled, lowered)."""
    pspecs = param_specs(arch, mesh)
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_cfg = adamw.AdamWConfig()
            # microbatching: pick the per-device microbatch so the remat'd
            # per-layer residual stack ([L, mb, S, d] bf16) stays ~<= 5 GB
            # (MoE additionally capped at 2 — capacity buffers dominate)
            dp = int(np.prod([mesh.shape[a] for a in batch_axes(mesh)]))
            resid_per_seq = 2.0 * arch.n_layers * shape.seq_len * arch.d_model
            per_dev = int(max(1, min(8, (5 * 1024 ** 3) // resid_per_seq)))
            if arch.uses_moe:
                per_dev = min(per_dev, 2)
            accum = max(1, shape.global_batch // (dp * per_dev))
            step_fn = make_train_step(arch, opt_cfg, use_kernel=use_kernel,
                                      unroll=unroll, accum=accum)
            state_specs = TrainState(
                params=pspecs,
                opt=adamw.OptState(mu=pspecs, nu=pspecs, count=P()))
            bspecs = data_specs(arch, shape, mesh)
            st_sds = abstract_train_state(arch)
            b_sds = input_specs(arch, shape)
            bspecs = {k: bspecs[k if k != "embeds" else "embeds"] for k in b_sds}
            jf = jax.jit(step_fn,
                         in_shardings=(to_shardings(state_specs, mesh),
                                       to_shardings(bspecs, mesh)),
                         out_shardings=(to_shardings(state_specs, mesh), None))
            lowered = jf.lower(st_sds, b_sds)
        elif shape.kind == "prefill":
            from repro.train import make_prefill_step
            step_fn = make_prefill_step(arch, use_kernel=use_kernel,
                                        unroll=unroll)
            b_sds = input_specs(arch, shape)
            bspecs = data_specs(arch, shape, mesh)
            key = "embeds" if arch.frontend in ("audio", "vlm") else "tokens"
            jf = jax.jit(step_fn,
                         in_shardings=(to_shardings(pspecs, mesh),
                                       to_shardings(bspecs[key], mesh)),
                         out_shardings=None)
            lowered = jf.lower(abstract(arch), b_sds[key])
        else:  # decode
            step_fn = make_serve_step(arch, use_kernel=use_kernel,
                                      unroll=unroll)
            dstate = abstract_decode_state(arch, shape)
            dspecs = decode_state_specs(arch, shape, mesh)
            b_ax = batch_axes(mesh)
            dp = int(np.prod([mesh.shape[a] for a in b_ax]))
            bspec = b_ax if shape.global_batch % dp == 0 else None
            out_tok_spec = P(bspec)                   # next-token ids [B]
            in_tok_spec = (P(bspec, None)             # stub-frontend embeds
                           if arch.frontend in ("audio", "vlm")
                           else P(bspec))
            t_sds = input_specs(arch, shape)["tokens"]
            jf = jax.jit(step_fn,
                         in_shardings=(to_shardings(pspecs, mesh),
                                       to_shardings(dspecs, mesh),
                                       NamedSharding(mesh, in_tok_spec)),
                         out_shardings=(NamedSharding(mesh, out_tok_spec),
                                        None, to_shardings(dspecs, mesh)))
            lowered = jf.lower(abstract(arch), dstate, t_sds)
        compiled = lowered.compile()
    return compiled, lowered


def _reduced_layers(arch: ArchConfig, units: int) -> ArchConfig:
    """Same-width model with `units` layer units (hybrid unit = one group)."""
    if arch.family == "hybrid":
        return arch.replace(n_layers=units * arch.shared_attn_every)
    return arch.replace(n_layers=units)


def _layer_units(arch: ArchConfig) -> int:
    return (arch.n_layers // arch.shared_attn_every
            if arch.family == "hybrid" else arch.n_layers)


def delta_costs(arch: ArchConfig, shape: ShapeConfig, mesh, *,
                use_kernel: bool = False) -> Dict:
    """Per-layer HLO costs via the 2-vs-4-layer-unrolled delta (XLA counts
    scan bodies once, so the full model's scanned HLO undercounts; the
    unrolled reduced models give exact per-layer collective/flop deltas
    that extrapolate linearly in depth)."""
    a_units, b_units = (1, 2) if arch.family == "hybrid" else (2, 4)
    out = {}
    for tag, units in (("a", a_units), ("b", b_units)):
        red = _reduced_layers(arch, units)
        compiled, _ = lower_cell(red, shape, mesh, use_kernel=use_kernel,
                                 unroll=True)
        txt = compiled.as_text()
        cost = compiled.cost_analysis()
        out[tag] = {"units": units,
                    "coll": collective_bytes(txt)["total"],
                    "coll_by_kind": collective_bytes(txt),
                    "flops": float(cost.get("flops", 0.0)),
                    "bytes": float(cost.get("bytes accessed", 0.0))}
    total = _layer_units(arch)
    span = b_units - a_units

    def extrap(key):
        per = (out["b"][key] - out["a"][key]) / span
        return out["a"][key] + (total - a_units) * per

    return {"collective_bytes_per_device": max(extrap("coll"), 0.0),
            "hlo_flops_extrap": max(extrap("flops"), 0.0),
            "hlo_bytes_extrap": max(extrap("bytes"), 0.0),
            "per_layer_collective": (out["b"]["coll"] - out["a"]["coll"]) / span,
            "samples": out}


def roofline(arch: ArchConfig, shape: ShapeConfig, mesh, compiled_full,
             deltas: Dict) -> Dict:
    from repro.launch import analytic
    n_chips = int(np.prod(list(mesh.shape.values())))
    mem = compiled_full.memory_analysis()

    flops = analytic.cell_flops(arch, shape)
    bytes_acc = analytic.cell_bytes(arch, shape)
    coll = deltas["collective_bytes_per_device"]

    t_compute = flops / (n_chips * PEAK_FLOPS)
    t_memory = bytes_acc / (n_chips * HBM_BW)
    t_coll = coll / ICI_BW                      # per-device HLO bytes
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]

    mdl = analytic.model_flops(arch, shape)
    # HLO flops are per-device; scale to global for the comparison
    hlo_flops_global = deltas["hlo_flops_extrap"] * n_chips
    bound = max(t_compute, t_memory, t_coll)
    used = getattr(mem, "temp_size_in_bytes", 0) \
        + getattr(mem, "argument_size_in_bytes", 0)

    return {
        "arch": arch.name, "shape": shape.name, "chips": n_chips,
        "params": n_params(arch),
        "analytic_flops": flops, "analytic_bytes": bytes_acc,
        "hlo_flops_extrap_global": hlo_flops_global,
        "collective_bytes_per_device": coll,
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "roofline_fraction": t_compute / bound if bound else 0.0,
        "model_flops": mdl,
        "useful_flops_ratio": mdl / flops if flops else 0.0,
        "bytes_per_device": int(used),
        "fits_hbm": used < HBM_BYTES,
        # The CPU backend promotes bf16 buffers to full f32 copies before
        # compute (dots/converts are f32 on CPU), roughly doubling temp
        # next to a real TPU executable; report the corrected estimate too.
        "bytes_per_device_bf16_est": int(getattr(mem, "argument_size_in_bytes", 0)
                                         + getattr(mem, "temp_size_in_bytes", 0) / 2),
        "fits_hbm_bf16_est": (getattr(mem, "argument_size_in_bytes", 0)
                              + getattr(mem, "temp_size_in_bytes", 0) / 2) < HBM_BYTES,
        "per_layer_collective": deltas["per_layer_collective"],
    }


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool = False,
             use_kernel: bool = False, verbose: bool = True,
             skip_deltas: bool = False) -> Dict:
    arch = cfgs.get(arch_name)
    shape = {s.name: s for s in cfgs.ALL_SHAPES}[shape_name]
    if shape.name == "long_500k" and not arch.sub_quadratic:
        return {"arch": arch.name, "shape": shape.name,
                "multi_pod": multi_pod,
                "skipped": "full attention is O(L^2) at 500k context "
                           "(DESIGN.md §Arch-applicability)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.monotonic()
    compiled, lowered = lower_cell(arch, shape, mesh, use_kernel=use_kernel)
    dt = time.monotonic() - t0
    if skip_deltas:
        deltas = {"collective_bytes_per_device": 0.0, "hlo_flops_extrap": 0.0,
                  "hlo_bytes_extrap": 0.0, "per_layer_collective": 0.0}
    else:
        deltas = delta_costs(arch, shape, mesh, use_kernel=use_kernel)
    rep = roofline(arch, shape, mesh, compiled, deltas)
    rep["compile_s"] = dt
    rep["multi_pod"] = multi_pod
    if verbose:
        mem = compiled.memory_analysis()
        print(f"[{arch.name} x {shape.name} x "
              f"{'2x16x16' if multi_pod else '16x16'}] compiled in {dt:.1f}s")
        print(f"  memory_analysis: {mem}")
        print(f"  roofline: compute={rep['t_compute_s']:.4f}s "
              f"memory={rep['t_memory_s']:.4f}s "
              f"collective={rep['t_collective_s']:.4f}s "
              f"-> {rep['dominant']}-bound; fits_hbm={rep['fits_hbm']} "
              f"roofline_fraction={rep['roofline_fraction']:.2f}")
    return rep


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--kernels", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    results = []
    if args.all:
        todo = [(a.name, s.name)
                for a in cfgs.ARCHS.values() for s in cfgs.ALL_SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    ok = True
    for arch_name, shape_name in todo:
        for mp in meshes:
            try:
                rep = run_cell(arch_name, shape_name, multi_pod=mp,
                               use_kernel=args.kernels)
                results.append(rep)
            except Exception as e:  # a failed cell is a bug in the system
                ok = False
                print(f"FAILED {arch_name} x {shape_name} "
                      f"(multi_pod={mp}): {type(e).__name__}: {e}")
                results.append({"arch": arch_name, "shape": shape_name,
                                "multi_pod": mp, "error": str(e)})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(wrap_results(results), f, indent=1)
        print(f"wrote {len(results)} cells to {args.out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
