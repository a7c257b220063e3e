"""Scoped 64-bit mode for the simulators.

The simulators run in 64-bit mode (times in seconds need more than f32's
7 digits to reproduce the oracle's FIFO tie-breaking), scoped to a
context manager so the rest of the framework stays in f32/bf16. XLA
compiles the f64 scan and exact bodies for TPU v5e too, where f64
arithmetic is emulated in software.

``REPRO_SIM_X64=0`` keeps the simulators out of 64-bit mode — the whole
simulation stack then runs in default f32. Scan-mode FIFO tie-breaking
loses its bit-faithfulness guarantee in f32, but scan must still agree
with exact mode within the golden fixture tolerance
(`tests/test_sweep_kernel.py::test_sweep_f32_within_golden_rtol` pins
this; every array-construction site pins its dtype via canonicalization
rather than f64 literals, so no row of a batch silently disagrees with
its neighbours about precision).
"""
from __future__ import annotations

import os

import jax


def x64_wanted() -> bool:
    """False when the operator pinned the simulators to f32
    (``REPRO_SIM_X64=0``). Read per call, so tests can flip it without
    reloading modules."""
    return os.environ.get("REPRO_SIM_X64", "1") != "0"


def enable_x64(enabled: bool = True):
    """Context manager switching JAX into 64-bit mode. With
    ``REPRO_SIM_X64=0`` the context is a no-op that *keeps* the default
    f32 world instead."""
    return jax.enable_x64(enabled and x64_wanted())
