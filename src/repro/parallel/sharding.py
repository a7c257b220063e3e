"""Sharding rules: logical parameter axes -> mesh axes -> PartitionSpecs.

Production meshes (see `repro.launch.mesh`):
    single-pod  (16, 16)        axes ("data", "model")
    multi-pod   (2, 16, 16)     axes ("pod", "data", "model")

Baseline strategy (the §Perf baseline; hillclimbed variants layer explicit
constraints on top):
  * weights tensor-parallel on the "model" axis along dimensions that are
    divisible by 16 for every assigned config: flattened head dims
    (H*hd, K*hd), d_ff, vocab (padded to 256), d_inner, expert count
    (when divisible, EP; otherwise TP on the expert FFN dim),
  * batch data-parallel over ("pod", "data"); the B=1 long-context shape
    shards the sequence over "data" instead,
  * decode KV caches shard kv-heads on "model" when divisible, else
    head_dim (always 128-divisible).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ArchConfig, ShapeConfig
from repro.models.layers import ParamDef, is_def
from repro.models.model import model_defs, padded_vocab


def _divisible(n: int, mesh: Mesh, axis: str) -> bool:
    return axis in mesh.shape and n % mesh.shape[axis] == 0


def logical_rules(cfg: ArchConfig, mesh: Mesh) -> Dict[str, Optional[str]]:
    """Map each logical axis name to a mesh axis (or None = replicate)."""
    rules: Dict[str, Optional[str]] = {
        "embed": None, "vocab": "model", "heads_flat": "model",
        "kv_flat": "model", "ffn": "model", "experts": None,
        "experts_router": None, "ssm_in": "model", "ssm_inner": "model",
        "ssm_conv": "model", "ssm_heads": None, "ssm_state": None,
        "head_dim": None, "layers": None, "groups": None,
        "layers_inner": None, "conv": None,
    }
    if cfg.uses_moe:
        if _divisible(cfg.n_experts, mesh, "model"):
            rules["experts"] = "model"      # expert parallelism
            rules["ffn"] = None
        # else: TP over the expert FFN dim (rules["ffn"] stays "model")
    # guard every rule by divisibility of the actual dims
    return rules


FSDP_THRESHOLD_BYTES = 8 * 1024 ** 3     # params+opt per device before FSDP kicks in


def param_specs(cfg: ArchConfig, mesh: Mesh):
    """PartitionSpec pytree matching `model_defs(cfg)`.

    One dimension of every weight is tensor-parallel on "model" (per
    `logical_rules`). When params+optimizer state would exceed
    FSDP_THRESHOLD_BYTES per device, a second dimension is fully-sharded
    over "data" (ZeRO-3 style: XLA all-gathers weights per layer and
    reduce-scatters gradients)."""
    rules = logical_rules(cfg, mesh)
    defs = model_defs(cfg)
    total_bytes = 12.0 * sum(int(np.prod(d.shape))
                             for d in jax.tree.leaves(defs, is_leaf=is_def))
    fsdp = (total_bytes / mesh.shape["model"]) > FSDP_THRESHOLD_BYTES \
        and "data" in mesh.shape

    def spec(d: ParamDef) -> P:
        axes: list = []
        used = set()
        for dim, name in zip(d.shape, d.logical):
            ax = rules.get(name) if name else None
            if ax is not None and ax not in used and dim % mesh.shape[ax] == 0:
                axes.append(ax)
                used.add(ax)
            else:
                axes.append(None)
        if fsdp and "data" not in used and len(d.shape) >= 2:
            # biggest still-unsharded divisible dim -> "data"
            cand = [(dim, i) for i, (dim, ax) in enumerate(zip(d.shape, axes))
                    if ax is None and dim % mesh.shape["data"] == 0
                    and d.logical[i] not in ("layers", "groups", "layers_inner")]
            if cand:
                _, i = max(cand)
                axes[i] = "data"
        return P(*axes)

    return jax.tree.map(spec, defs, is_leaf=is_def)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def data_specs(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh):
    """Specs for one training/prefill batch dict."""
    b_ax = batch_axes(mesh)
    dp = int(np.prod([mesh.shape[a] for a in b_ax]))
    if shape.global_batch % dp == 0:
        tok = P(b_ax, None)
    else:
        # B=1 long-context: shard the sequence instead
        tok = P(None, b_ax)
    if cfg.frontend in ("audio", "vlm"):
        return {"embeds": P(*tok, None), "labels": tok, "mask": tok}
    return {"tokens": tok, "labels": tok, "mask": tok}


def decode_state_specs(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh):
    """Specs mirroring `init_decode_state` (stacked leading layer/group dim)."""
    from repro.models.model import init_decode_state  # structure reference
    b_ax = batch_axes(mesh)
    dp = int(np.prod([mesh.shape[a] for a in b_ax]))
    batch_sharded = shape.global_batch % dp == 0
    bspec = b_ax if batch_sharded else None
    sspec = None if batch_sharded else b_ax      # B=1: shard cache seq on data

    # KV cache [L, B, S, K, hd]: shard kv-heads on "model" when divisible;
    # otherwise shard the SEQUENCE on "model" (flash-decode style: each
    # shard attends its KV slice, softmax stats combine via tiny psums —
    # far cheaper than re-gathering the cache every layer).
    if cfg.n_kv_heads % mesh.shape["model"] == 0:
        kv_head_ax: Optional[str] = "model"
        seq_axes = sspec
    else:
        kv_head_ax = None
        seq_axes = (("model",) if sspec is None
                    else tuple(sspec) + ("model",))

    kv_spec = P(None, bspec, seq_axes, kv_head_ax, None)   # [L, B, S, K, hd]
    len_spec = P()
    ssm_h_ax = "model" if cfg.ssm_heads and cfg.ssm_heads % mesh.shape["model"] == 0 else None
    conv_dim = cfg.d_inner + 2 * cfg.ssm_state
    conv_ax = "model" if conv_dim and conv_dim % mesh.shape["model"] == 0 else None

    specs_kv = None
    specs_ssm = None
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        from repro.models.transformer import KVCache
        specs_kv = KVCache(kv_spec, kv_spec, len_spec)
    elif cfg.family == "ssm":
        from repro.models.ssm import SSMState
        specs_ssm = SSMState(h=P(None, bspec, ssm_h_ax, None, None),
                             conv=P(None, bspec, None, conv_ax))
    elif cfg.family == "hybrid":
        from repro.models.ssm import SSMState
        from repro.models.transformer import KVCache
        specs_kv = KVCache(kv_spec, kv_spec, len_spec)
        specs_ssm = SSMState(h=P(None, None, bspec, ssm_h_ax, None, None),
                             conv=P(None, None, bspec, None, conv_ax))
    from repro.models.model import DecodeState
    return DecodeState(kv=specs_kv, ssm=specs_ssm, pos=P())


def to_shardings(tree_specs, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


SEQ_SHARD_ACTIVATIONS = False   # §Perf L6: measured 4x collective regression
# (per-layer AG/RS of the residual in f32 on this backend); keep activations
# batch-sharded and control remat liveness via microbatch size instead.


def current_mesh():
    """The ambient mesh (set by `jax.set_mesh`), or None."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not mesh.axis_names:
        return None
    return mesh


def constrain_activations(x):
    """Residual-stream constraint [B, S, d]: batch on the data axes (and,
    if SEQ_SHARD_ACTIVATIONS, sequence on "model" — measured counter-
    productive, see §Perf L6, kept as a switch for re-evaluation on real
    ICI)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    b_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = int(np.prod([mesh.shape[a] for a in b_ax])) if b_ax else 1
    axes: list = [None] * x.ndim
    if b_ax and x.shape[0] % dp == 0:
        axes[0] = b_ax
    if (SEQ_SHARD_ACTIVATIONS and x.ndim >= 3 and "model" in mesh.axis_names
            and x.shape[1] % mesh.shape["model"] == 0 and x.shape[1] > 1):
        axes[1] = "model"
    return jax.lax.with_sharding_constraint(x, P(*axes))


def constrain_batch_dim(tree, dim: int = 0):
    """with_sharding_constraint: shard `dim` of every leaf over the data
    axes of the current mesh (no-op without a mesh or when indivisible).
    Used after reshapes that would otherwise lose batch sharding (e.g. the
    microbatch split in gradient accumulation)."""
    mesh = current_mesh()
    if mesh is None:
        return tree
    b_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not b_ax:
        return tree
    dp = int(np.prod([mesh.shape[a] for a in b_ax]))

    def one(x):
        if x.ndim <= dim or x.shape[dim] % dp != 0:
            return x
        axes = [None] * x.ndim
        axes[dim] = b_ax
        return jax.lax.with_sharding_constraint(x, P(*axes))

    return jax.tree.map(one, tree)


def constrain_decode_kv(x):
    """KV-cache constraint [B, S, K, hd], mirroring `decode_state_specs`:
    kv-heads on "model" when divisible, else the sequence (flash-decode
    style). Applied right after the decode `dynamic_update_slice` — the
    partitioner otherwise reshards the updated cache mid-layer (observed
    as involuntary full rematerializations, i.e. per-layer cache
    all-gathers)."""
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names or x.ndim != 4:
        return x
    b_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = int(np.prod([mesh.shape[a] for a in b_ax])) if b_ax else 1
    batch_sharded = bool(b_ax) and x.shape[0] % dp == 0
    bspec = b_ax if batch_sharded else None
    sspec = None if batch_sharded else (b_ax or None)
    if x.shape[2] % mesh.shape["model"] == 0:
        kv_head_ax: Optional[str] = "model"
        seq_axes = sspec
    else:
        kv_head_ax = None
        seq_axes = ("model",) if sspec is None else tuple(sspec) + ("model",)
    return jax.lax.with_sharding_constraint(x, P(bspec, seq_axes, kv_head_ax, None))
