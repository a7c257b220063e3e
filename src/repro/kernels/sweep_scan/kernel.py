"""Pallas kernel for the sweep simulator's scan hot loop.

One grid step serves one VMEM block of a candidate's padded op rows
(grid = (C, N // block_rows), block axis minormost so it executes
sequentially per candidate). The FIFO carry — per-resource availability,
per-op completion times and the running makespan — lives in VMEM scratch
and persists across the block steps of a candidate, exactly like the
online-softmax state in `kernels/flash_attention`. The completion-time
scratch spans the full op axis (a dep may point at any earlier op, and
in scan-approximation mode even a not-yet-served one, which reads as
0.0 — the same semantics as the `lax.scan` carry in `ref.scan_serve`).

The serving recurrence is scalar and sequential by construction (each
op's start depends on the previous op on its resource), so the win over
the XLA `lax.scan` is not vectorization but fusion: one kernel per
bucket streams every per-op operand HBM->VMEM block-wise exactly once,
with no per-step loop-carried tuple shuffling. Every arithmetic step
(max chains and adds) is performed in the same order as the reference,
so results are bit-identical, not approximately equal
(tests/test_sweep_kernel.py asserts element-wise equality).

The kernel runs only in interpret mode on CPU, where the tests hold it
bit-identical to the reference. Mosaic refuses it for TPU (see
`ops.TPU_REFUSAL`), so the engine builds TPU scan executables on XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# default VMEM block over the padded-op-row axis: buckets are pow2 with
# floor 16 (sweep.buckets), so any pow2 block <= N divides N evenly
BLOCK_ROWS = 256


def _kernel(res_ref, dur_ref, lag_ref, deps_ref, mk_ref, end_ref,
            avail_scr, end_scr, mk_scr, *, block: int, n_blocks: int,
            maxd: int):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        avail_scr[...] = jnp.zeros_like(avail_scr)
        end_scr[...] = jnp.zeros_like(end_scr)
        mk_scr[...] = jnp.zeros_like(mk_scr)

    base = b * block

    def step(i, mk):
        r = res_ref[0, i]
        d = dur_ref[0, i]
        # ready time: max over dep completion times (completion scratch
        # holds 0.0 for unserved ops — scan-order approximation
        # semantics). maxd is static and tiny (MAXD=4): unrolled.
        ready = jnp.zeros((), d.dtype)
        for j in range(maxd):
            dep = deps_ref[0, i, j]
            e = jnp.where(dep >= 0, end_scr[jnp.maximum(dep, 0)], 0.0)
            ready = jnp.maximum(ready, e)
        start = jnp.maximum(ready, avail_scr[r])
        fin = start + d
        avail_scr[r] = fin
        end_scr[base + i] = fin + lag_ref[0, i]
        return jnp.maximum(mk, fin)

    mk = jax.lax.fori_loop(0, block, step, mk_scr[0])
    mk_scr[0] = mk
    end_ref[0, :] = end_scr[pl.ds(base, block)]

    @pl.when(b == n_blocks - 1)
    def _finalize():
        mk_ref[0] = mk


def sweep_scan_kernel(res: jax.Array, dur: jax.Array, lag: jax.Array,
                      deps: jax.Array, *, n_resources: int,
                      block_rows: int = BLOCK_ROWS
                      ) -> tuple[jax.Array, jax.Array]:
    """res i32[C, N], dur/lag f[C, N], deps i32[C, N, MAXD] ->
    (makespan f[C], end f[C, N]), in interpret mode. N must divide by
    the effective block (always true for the engine's pow2 buckets)."""
    C, N = res.shape
    maxd = deps.shape[-1]
    block = min(block_rows, N)
    assert N % block == 0, f"op rows {N} not divisible by block {block}"
    n_blocks = N // block

    grid = (C, n_blocks)
    return pl.pallas_call(
        functools.partial(_kernel, block=block, n_blocks=n_blocks,
                          maxd=maxd),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block), lambda c, b: (c, b)),
            pl.BlockSpec((1, block), lambda c, b: (c, b)),
            pl.BlockSpec((1, block), lambda c, b: (c, b)),
            pl.BlockSpec((1, block, maxd), lambda c, b: (c, b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1,), lambda c, b: (c,)),
            pl.BlockSpec((1, block), lambda c, b: (c, b)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C,), dur.dtype),
            jax.ShapeDtypeStruct((C, N), dur.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_resources,), dur.dtype),   # FIFO availability
            pltpu.VMEM((N,), dur.dtype),             # completion times
            pltpu.VMEM((1,), dur.dtype),             # running makespan
        ],
        interpret=True,
    )(res, dur, lag, deps)
