"""Dry-run machinery tests: HLO collective parser, analytic FLOP model
cross-check, input specs, and one real (subprocess) cell compile."""
import json
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfgs
from repro.launch import analytic
from repro.models import init, loss_fn
from repro.models.config import ShapeConfig, TRAIN_4K

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_collective_parser():
    from repro.launch.dryrun import _shape_bytes, collective_bytes
    assert _shape_bytes("f32[4,8]") == 128
    assert _shape_bytes("bf16[2,3,4]") == 48
    assert _shape_bytes("(f32[4], s32[2])") == 24
    hlo = """
      a = f32[16,128]{1,0} all-reduce(b), replica_groups={}
      c = bf16[8,64]{1,0} all-gather(d), dimensions={0}
      e = (f32[4,4]{1,0}, f32[4,4]{1,0}) all-to-all(f, g)
      h = f32[32]{0} collective-permute-start(i)
    """
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 16 * 128 * 4 * 2.0        # ring 2x
    assert out["all-gather"] == 8 * 64 * 2
    assert out["all-to-all"] == 2 * 16 * 4
    assert out["collective-permute"] == 128
    assert out["total"] == sum(v for k, v in out.items() if k != "total")


def test_analytic_flops_match_hlo_on_small_dense():
    """Closed-form forward FLOPs vs XLA cost analysis on an unrolled tiny
    dense model (single device, full attention materialized by blocks)."""
    cfg = cfgs.get("granite-3-2b").reduced()
    shape = ShapeConfig("t", 64, 2, "prefill")
    params = init(jax.random.PRNGKey(0), cfg)
    from repro.models import forward
    f = jax.jit(lambda p, t: forward(p, t, cfg, remat=False, unroll=True))
    toks = jnp.zeros((2, 64), jnp.int32)
    comp = f.lower(params, toks).compile()
    hlo_flops = float(comp.cost_analysis().get("flops", 0.0))
    ours = analytic.forward_flops(cfg, 2, 64)
    # bf16 promotion/fusions make exact equality impossible; within 2x and
    # same order of magnitude is the guard we need for roofline sanity
    assert ours == pytest.approx(hlo_flops, rel=1.0), (ours, hlo_flops)
    assert ours > 0.3 * hlo_flops


def test_model_flops_reference():
    arch = cfgs.get("granite-3-2b")
    mf = analytic.model_flops(arch, TRAIN_4K)
    from repro.models import n_params
    assert mf == pytest.approx(6.0 * n_params(arch) * 4096 * 256)
    # MoE uses active params only
    moe = cfgs.get("mixtral-8x22b")
    mf_moe = analytic.model_flops(moe, TRAIN_4K)
    from repro.models import n_params as npar
    assert mf_moe < 6.0 * npar(moe) * 4096 * 256


def test_cell_flops_ordering():
    """train > prefill > decode for the same arch; moe decode ~ active."""
    a = cfgs.get("granite-3-2b")
    from repro.models.config import DECODE_32K, PREFILL_32K
    t = analytic.cell_flops(a, TRAIN_4K)
    p = analytic.cell_flops(a, PREFILL_32K)
    d = analytic.cell_flops(a, DECODE_32K)
    assert t > p > d > 0


def test_input_specs_cover_all_cells():
    import importlib
    dr = importlib.import_module("repro.launch.dryrun")
    for arch in cfgs.ARCHS.values():
        for shape in cfgs.cells(arch):
            specs = dr.input_specs(arch, shape)
            assert "tokens" in specs or "embeds" in specs
            for v in specs.values():
                assert isinstance(v, jax.ShapeDtypeStruct)


@pytest.mark.slow
def test_one_real_dryrun_cell_compiles():
    """Subprocess (needs 512 virtual devices before jax init): the
    fastest real cell must lower + compile + report roofline terms."""
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tmp:
        path = tmp.name
    try:
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch",
             "granite-3-2b", "--shape", "decode_32k", "--out", path],
            capture_output=True, text=True, timeout=560,
            env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
            cwd=REPO)
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
        from repro.launch.dryrun_meta import unwrap_results
        with open(path) as f:
            cells, stale = unwrap_results(json.load(f))
        assert not stale, f"dry-run wrote a stale artifact: {stale}"
        rep = cells[0]
        assert rep["fits_hbm"] and rep["dominant"] == "memory"
        assert rep["chips"] == 256
    finally:
        os.unlink(path)
