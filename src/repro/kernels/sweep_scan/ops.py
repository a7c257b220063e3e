"""Public wrapper: trace-time dispatch between the fused Pallas kernel
and the pure-XLA reference.

`sweep_scan` is what `SweepEngine` builds its scan-mode executables on
(behind the ``sim_engine`` knob). Dispatch happens at trace time —
`kernel_refusal()` is an ordinary Python predicate evaluated while the
executable is being built, so a backend the kernel cannot run on traces
the reference path instead of failing at run time.

Where the kernel runs is decided here, from the platform, and nowhere
else (`kernel_refusal`): on CPU it runs in interpret mode, a correctness
harness for the tests and not a speedup. On TPU the chip's compiler
refuses it, so the TPU scan body is XLA's ``lax.scan``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax

from .kernel import BLOCK_ROWS, sweep_scan_kernel
from .ref import sweep_scan_ref

# Why Mosaic (the TPU Pallas compiler) refuses `kernel.sweep_scan_kernel`,
# as compiled ahead of time for v5e in f32 and f64: the ``(1, block)``
# BlockSpecs over ``(C, N)`` break the (8, 128) tiling rule ("last two
# dimensions of your block shape are divisible by 8 and 128"), and at
# C=1 the scalar scratch stores fail with "Cannot store scalars to VMEM".
# The kernel is a scalar loop over an N-sized completion table read at
# random; making it lower is a redesign, not a fix.
TPU_REFUSAL = ("Mosaic refuses the sweep_scan kernel on TPU: its (1, block) "
               "BlockSpecs over (C, N) break the (8, 128) tiling rule, and "
               "its scalar scratch stores cannot be placed in VMEM")


class Refusal(NamedTuple):
    """Why the kernel cannot run on a backend. ``fallback`` says whether
    the XLA body taken instead counts as a fallback: not on TPU, where
    XLA is the platform's own scan body by design."""
    reason: str
    fallback: bool


def kernel_refusal() -> Optional[Refusal]:
    """Why the kernel cannot run on the current backend, or None where
    it can (CPU, in interpret mode). Evaluated at trace time by the
    engine's executable builder."""
    platform = jax.default_backend()
    if platform == "cpu":
        return None
    if platform == "tpu":
        return Refusal(TPU_REFUSAL, fallback=False)
    return Refusal(f"the kernel has no Pallas lowering for backend "
                   f"{platform!r}", fallback=True)


def sweep_scan(res: jax.Array, dur: jax.Array, lag: jax.Array,
               deps: jax.Array, *, n_resources: int, use_kernel: bool,
               block_rows: int = BLOCK_ROWS
               ) -> tuple[jax.Array, jax.Array]:
    """Batched FIFO scan: res i32[C, N], dur/lag f[C, N],
    deps i32[C, N, MAXD] -> (makespan f[C], end f[C, N]).

    ``use_kernel`` is decided by the caller (the engine resolves its
    ``sim_engine`` knob against `kernel_refusal`); both paths are
    element-wise identical.
    """
    if not use_kernel:
        return sweep_scan_ref(res, dur, lag, deps, n_resources=n_resources)
    return sweep_scan_kernel(res, dur, lag, deps, n_resources=n_resources,
                             block_rows=block_rows)
