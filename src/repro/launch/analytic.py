"""Analytic FLOP/byte model per (arch x shape) — the primary §Roofline
compute/memory terms.

Why analytic and not `cost_analysis()` alone: XLA's cost analysis counts a
`while`/`scan` body ONCE regardless of trip count, so any scanned loop
(layer stack, blocked attention, SSD chunk scan) is undercounted. The
dry-run therefore (a) uses these closed-form counts for compute/memory,
(b) extracts collective bytes from compiled HLO via a 2-vs-4-layer
unrolled delta (collectives sit at layer boundaries, outside inner
scans), and (c) cross-checks (a) against the same unrolled-delta HLO
flops (`tests/test_dryrun_smoke.py`).
"""
from __future__ import annotations

from repro.models.config import ArchConfig, ShapeConfig


def _attn_flops(arch: ArchConfig, B: int, Sq: int, Skv: int, *,
                causal: bool) -> float:
    H, K, hd, d = arch.n_heads, arch.n_kv_heads, arch.head_dim, arch.d_model
    if H == 0:
        return 0.0
    proj = 2.0 * B * Sq * d * (H * hd) + 2 * (2.0 * B * Sq * d * (K * hd))
    o = 2.0 * B * Sq * (H * hd) * d
    eff_kv = min(Skv, arch.window) if arch.window else Skv
    pairs = B * Sq * eff_kv * (0.5 if (causal and Sq == Skv and not arch.window) else 1.0)
    core = 2.0 * pairs * H * hd * 2          # QK^T and PV
    return proj + o + core


def _ffn_flops(arch: ArchConfig, B: int, S: int) -> float:
    d = arch.d_model
    if arch.uses_moe:
        router = 2.0 * B * S * d * arch.n_experts
        # top_k experts per token, capacity_factor head-room is zero-padded
        # compute in the static dispatch — count it (it burns real MXU time)
        tokens = B * S * arch.top_k * arch.capacity_factor
        return router + 3 * 2.0 * tokens * d * arch.d_ff
    return 3 * 2.0 * B * S * d * arch.d_ff


def _ssd_flops(arch: ArchConfig, B: int, S: int) -> float:
    d, di, N, H = arch.d_model, arch.d_inner, arch.ssm_state, arch.ssm_heads
    L = min(arch.ssm_chunk, S)
    proj = 2.0 * B * S * d * (2 * di + 2 * N + H) + 2.0 * B * S * di * d
    conv = 2.0 * B * S * (di + 2 * N) * 4
    scores = 2.0 * B * S * L * N              # C.B^T per chunk
    intra = 2.0 * B * S * L * di              # w @ (dt x)
    states = 2 * 2.0 * B * S * N * di         # chunk states + y_inter
    return proj + conv + scores + intra + states


def _ssd_decode_flops(arch: ArchConfig, B: int) -> float:
    d, di, N, H = arch.d_model, arch.d_inner, arch.ssm_state, arch.ssm_heads
    proj = 2.0 * B * d * (2 * di + 2 * N + H) + 2.0 * B * di * d
    state = 2 * 2.0 * B * di * N              # state update + readout
    return proj + state


def _attn_decode_flops(arch: ArchConfig, B: int, Skv: int) -> float:
    H, K, hd, d = arch.n_heads, arch.n_kv_heads, arch.head_dim, arch.d_model
    if H == 0:
        return 0.0
    eff = min(Skv, arch.window) if arch.window else Skv
    proj = 2.0 * B * d * (H + 2 * K) * hd + 2.0 * B * (H * hd) * d
    core = 2 * 2.0 * B * eff * H * hd
    return proj + core


def forward_flops(arch: ArchConfig, B: int, S: int, *, decode: bool = False,
                  ctx: int = 0) -> float:
    """One forward pass, all layers + head. decode: S==1 vs a ctx cache."""
    from repro.models.model import padded_vocab
    head = 2.0 * B * (1 if decode else S) * arch.d_model * padded_vocab(arch.vocab)
    total = head
    if arch.family in ("dense", "moe", "audio", "vlm"):
        per = (_attn_decode_flops(arch, B, ctx) if decode
               else _attn_flops(arch, B, S, S, causal=True))
        per += (_ffn_flops(arch, B, 1) if decode else _ffn_flops(arch, B, S))
        total += arch.n_layers * per
    elif arch.family == "ssm":
        per = (_ssd_decode_flops(arch, B) if decode
               else _ssd_flops(arch, B, S))
        total += arch.n_layers * per
    elif arch.family == "hybrid":
        per = (_ssd_decode_flops(arch, B) if decode
               else _ssd_flops(arch, B, S))
        total += arch.n_layers * per
        n_groups = arch.n_layers // arch.shared_attn_every
        shared = (_attn_decode_flops(arch, B, ctx) if decode
                  else _attn_flops(arch, B, S, S, causal=True))
        shared += (_ffn_flops(arch, B, 1) if decode else _ffn_flops(arch, B, S))
        total += n_groups * shared
    return total


def cell_flops(arch: ArchConfig, shape: ShapeConfig, *, remat: bool = True) -> float:
    """Total HLO-grade flops for one step of this cell."""
    from repro.models.model import model_defs
    from repro.models.layers import count_params
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        fwd = forward_flops(arch, B, S)
        n = count_params(model_defs(arch))
        opt = 10.0 * n                       # AdamW update
        mult = 4.0 if remat else 3.0         # fwd + 2x bwd (+1 remat fwd)
        return mult * fwd + opt
    if shape.kind == "prefill":
        return forward_flops(arch, B, S)
    return forward_flops(arch, B, 1, decode=True, ctx=S)


def cell_bytes(arch: ArchConfig, shape: ShapeConfig) -> float:
    """HBM traffic (global, all chips) for one step — napkin model:
    weights + optimizer state + activations (+ KV cache for decode)."""
    from repro.models.model import model_defs
    from repro.models.layers import count_params
    n = count_params(model_defs(arch))
    B, S = shape.global_batch, shape.seq_len
    d = arch.d_model
    act_bytes = 2.0  # bf16
    if shape.kind == "train":
        # params f32 read (fwd+bwd+remat ~ 3x), grads + adam m/v read+write
        w = n * 4.0 * (3 + 1 + 4)
        acts = 3.0 * B * S * d * arch.n_layers * act_bytes * 4  # remat'd residuals
        return w + acts
    if shape.kind == "prefill":
        return n * 2.0 + 8.0 * B * S * d * arch.n_layers * act_bytes
    # decode: weights (active) + cache read/write
    n_active = n
    if arch.uses_moe:
        n_active = n - arch.n_layers * (arch.n_experts - arch.top_k) * 3 * d * arch.d_ff
        n_active += arch.n_layers * min(B * arch.top_k, arch.n_experts) * 3 * d * arch.d_ff
        n_active = min(n_active, n)
    cache = 0.0
    if arch.uses_attention:
        eff = min(S, arch.window) if arch.window else S
        n_attn = (arch.n_layers if arch.family in ("dense", "moe", "audio", "vlm")
                  else arch.n_layers // arch.shared_attn_every)
        cache = n_attn * B * eff * arch.n_kv_heads * arch.head_dim * 2 * act_bytes
    if arch.ssm_state:
        P = arch.d_inner // arch.ssm_heads
        cache += 2 * arch.n_layers * B * arch.ssm_heads * arch.ssm_state * P * 4.0
    return n_active * 2.0 + cache


def model_flops(arch: ArchConfig, shape: ShapeConfig) -> float:
    """The 6·N·D (train) / 2·N_active·D (inference) reference."""
    from repro.models.model import model_defs
    from repro.models.layers import count_params
    n = count_params(model_defs(arch))
    if arch.uses_moe:
        n = n - arch.n_layers * (arch.n_experts - arch.top_k) * 3 \
            * arch.d_model * arch.d_ff
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    return (6.0 if shape.kind == "train" else 2.0) * n * tokens
