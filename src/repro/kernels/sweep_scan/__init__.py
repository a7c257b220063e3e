"""Fused FIFO service-time scan for the sweep engine's hot loop.

`ops.sweep_scan` is the public entry: a batched (candidate-major) port
of `repro.core.jax_sim._scan_once` that runs as one Pallas kernel with
explicit VMEM blocking over the padded-op-row axis (interpret mode, on
CPU only: `ops.kernel_refusal` says why not elsewhere), or as the
pure-XLA `ref.sweep_scan_ref`. Both paths are
element-wise identical (tests/test_sweep_kernel.py).
"""
from .ops import kernel_refusal, sweep_scan    # noqa: F401
from .ref import scan_serve, sweep_scan_ref    # noqa: F401
