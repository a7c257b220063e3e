"""Differential tier for the fused Pallas sweep-scan kernel
(repro.kernels.sweep_scan) and the engine's ``sim_engine`` knob.

The acceptance property is BIT-IDENTITY, not a tolerance: the kernel and
the XLA reference execute the same max/add sequence over the same
operands (the recurrence has one implementation, `ref.scan_serve`, that
both paths build on), so any elementwise difference is a bug. Covered
here:

  * raw kernel == reference over boundary padded-row shapes (1 op, one
    block minus/plus one, exact multi-block splits) and dep fan-in
    patterns — hypothesis-driven when installed, a seeded fixed grid
    otherwise;
  * `SweepEngine(sim_engine="pallas")` == ``"xla"`` through
    `simulate_batch` on all three shipped trace fixtures, healthy and
    faulted, across inline / sharded / multiproc backends;
  * the ``auto`` fallback: with Pallas unavailable the engine silently
    (but *countedly* — `CacheStats.kernel_fallbacks`) serves the XLA
    path, while ``"pallas"`` refuses;
  * the f32 escape hatch (``REPRO_SIM_X64=0``): scan and exact modes
    still agree within the golden fixture tolerance with the x64 shim
    disabled — the dtype-pinning audit's regression test.
"""
from pathlib import Path

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as hst
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.core import (MB, PAPER_RAMDISK, DiskDegradation, FaultScenario,
                        MultiprocBackend, NodeFailure, Predictor,
                        ShardedBackend, SweepEngine, SweepSession, grid,
                        with_faults)
from repro.core.compile import MAXD
from repro.core.sweep.engine import SIM_ENGINES
from repro.core.sweep import engine as engine_mod
from repro.core.trace import load_trace, to_workflow
from repro.core.x64 import enable_x64
from repro.kernels.sweep_scan import kernel_refusal, sweep_scan
from repro.kernels.sweep_scan.ops import Refusal
from repro.kernels.sweep_scan.ref import sweep_scan_ref

from test_trace import FIXTURE_SCAN_EXACT_RTOL

ST = PAPER_RAMDISK
TRACES = Path(__file__).resolve().parents[1] / "examples" / "traces"
FIXTURES = ["montage_small.json", "blast_small.json", "cycles_small.dax"]

FAULT_AXIS = (None,
              FaultScenario(degraded=(DiskDegradation(0, 8.0),), name="disk"),
              FaultScenario(failures=(NodeFailure(0, after_tasks=3),),
                            name="kill"))


def sweep_pairs(fixture, faults=None):
    wf = to_workflow(load_trace(TRACES / fixture))
    cands = grid(n_nodes=[7], chunk_sizes=[512 * 1024, 1 * MB])
    if faults is not None:
        cands = with_faults(cands, faults)
    return [wf] * len(cands), [c.to_config() for c in cands]


def random_bucket(n_ops, n_cand, n_res, seed):
    """A valid padded scan bucket: deps point strictly earlier or -1."""
    rng = np.random.default_rng(seed)
    res = rng.integers(0, n_res, (n_cand, n_ops), dtype=np.int32)
    dur = rng.uniform(0.01, 1.0, (n_cand, n_ops))
    lag = rng.uniform(0.0, 0.1, (n_cand, n_ops))
    deps = np.full((n_cand, n_ops, MAXD), -1, dtype=np.int32)
    for i in range(1, n_ops):
        k = int(rng.integers(0, MAXD + 1))
        if k:
            deps[:, i, :k] = rng.integers(0, i, (n_cand, k))
    return res, dur, lag, deps


def assert_kernel_matches_ref(n_ops, n_cand, n_res, seed, block_rows=256):
    res, dur, lag, deps = random_bucket(n_ops, n_cand, n_res, seed)
    with enable_x64():
        mk_k, end_k = sweep_scan(res, dur, lag, deps, n_resources=n_res,
                                 use_kernel=True, block_rows=block_rows)
        mk_r, end_r = sweep_scan_ref(res, dur, lag, deps, n_resources=n_res)
    np.testing.assert_array_equal(np.asarray(mk_k), np.asarray(mk_r))
    np.testing.assert_array_equal(np.asarray(end_k), np.asarray(end_r))


# boundary shapes around a block size of 8: one row, block-1, block,
# block+1 (single oversized block), and an exact multi-block split
# (2 blocks + 3 would violate the kernel's divisibility contract, which
# production never does — pow2 bucketing; the contract itself is pinned
# in test_indivisible_rows_rejected)
BOUNDARY = [(1, 1, 1, 0), (7, 3, 4, 1), (8, 2, 8, 2), (9, 5, 3, 3),
            (19, 4, 6, 4)]


@pytest.mark.parametrize("n_ops,n_cand,n_res,seed", BOUNDARY)
def test_kernel_matches_ref_boundary_shapes(n_ops, n_cand, n_res, seed):
    assert_kernel_matches_ref(n_ops, n_cand, n_res, seed)


@pytest.mark.parametrize("n_ops,block_rows", [(64, 16), (64, 64), (128, 32)])
def test_kernel_matches_ref_multi_block(n_ops, block_rows):
    """The VMEM-blocked path: several sequential grid steps per
    candidate, scratch state (avail, end) carried across blocks."""
    assert_kernel_matches_ref(n_ops, 4, 8, seed=n_ops,
                              block_rows=block_rows)


def test_indivisible_rows_rejected():
    res, dur, lag, deps = random_bucket(24, 2, 4, seed=0)
    with pytest.raises(AssertionError):
        sweep_scan(res, dur, lag, deps, n_resources=4, use_kernel=True,
                   block_rows=16)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(n_ops=hst.integers(1, 48), n_cand=hst.integers(1, 6),
           n_res=hst.integers(1, 9), seed=hst.integers(0, 2 ** 16))
    def test_kernel_matches_ref_property(n_ops, n_cand, n_res, seed):
        assert_kernel_matches_ref(n_ops, n_cand, n_res, seed)


# ---------------- engine-level differential ---------------------------------------

def _simulate(session, wfs, cfgs, exact=False):
    return np.asarray(session.simulate_batch(wfs, cfgs, st=ST, exact=exact))


@pytest.mark.parametrize("fixture", FIXTURES)
def test_engine_kernel_bit_identical_healthy(fixture):
    wfs, cfgs = sweep_pairs(fixture)
    with SweepSession(sim_engine="pallas") as sk, \
            SweepSession(sim_engine="xla") as sx:
        vk, vx = _simulate(sk, wfs, cfgs), _simulate(sx, wfs, cfgs)
        np.testing.assert_array_equal(vk, vx)
        assert sk.stats.kernel_buckets > 0
        assert sk.stats.kernel_fallbacks == 0
        assert sx.stats.kernel_buckets == 0


@pytest.mark.parametrize("fixture", FIXTURES)
def test_engine_kernel_bit_identical_faulted(fixture):
    wfs, cfgs = sweep_pairs(fixture, faults=FAULT_AXIS)
    with SweepSession(sim_engine="pallas") as sk, \
            SweepSession(sim_engine="xla") as sx:
        np.testing.assert_array_equal(_simulate(sk, wfs, cfgs),
                                      _simulate(sx, wfs, cfgs))
        faulted_kernel = [k for k in sk.engine.cache_keys() if k[5] and k[6]]
        assert faulted_kernel, "no faulted bucket took the kernel path"


def test_exact_mode_ignores_kernel_knob():
    """Exact mode always runs the XLA while_loop; a kernel session's
    exact pass must match the XLA session's and compile no kernel
    buckets for it."""
    wfs, cfgs = sweep_pairs("montage_small.json")
    with SweepSession(sim_engine="pallas") as sk, \
            SweepSession(sim_engine="xla") as sx:
        np.testing.assert_array_equal(_simulate(sk, wfs, cfgs, exact=True),
                                      _simulate(sx, wfs, cfgs, exact=True))
        assert sk.stats.kernel_buckets == 0


def test_sharded_backend_hits_kernel():
    wfs, cfgs = sweep_pairs("blast_small.json")
    with SweepSession(ShardedBackend(0, min_shard_oprows=0),
                      sim_engine="pallas") as sh, \
            SweepSession(sim_engine="xla") as sx:
        np.testing.assert_array_equal(_simulate(sh, wfs, cfgs),
                                      _simulate(sx, wfs, cfgs))
        assert sh.stats.kernel_buckets > 0


def test_multiproc_backend_hits_kernel():
    """Workers receive ``sim_engine`` in the item payload and their
    kernel counters roll up to the parent session."""
    wfs, cfgs = sweep_pairs("montage_small.json", faults=(None, FAULT_AXIS[1]))
    with SweepSession(MultiprocBackend(2), sim_engine="pallas") as mp, \
            SweepSession(sim_engine="xla") as sx:
        vm, vx = _simulate(mp, wfs, cfgs), _simulate(sx, wfs, cfgs)
        np.testing.assert_array_equal(vm, vx)
        assert mp.stats.kernel_buckets > 0, \
            "worker kernel counters did not roll up"


# ---------------- fallback & knob validation --------------------------------------

def test_auto_falls_back_counted(monkeypatch):
    monkeypatch.setattr(engine_mod.sweep_scan_ops, "kernel_refusal",
                        lambda: Refusal("no Pallas lowering here",
                                        fallback=True))
    wfs, cfgs = sweep_pairs("montage_small.json")
    with SweepSession(sim_engine="auto") as sa, \
            SweepSession(sim_engine="xla") as sx:
        np.testing.assert_array_equal(_simulate(sa, wfs, cfgs),
                                      _simulate(sx, wfs, cfgs))
        assert sa.stats.kernel_fallbacks > 0
        assert sa.stats.kernel_buckets == 0


def test_forced_pallas_raises_when_unsupported(monkeypatch):
    monkeypatch.setattr(engine_mod.sweep_scan_ops, "kernel_refusal",
                        lambda: Refusal("no Pallas lowering here",
                                        fallback=True))
    wfs, cfgs = sweep_pairs("montage_small.json")
    with SweepSession(sim_engine="pallas") as sess:
        with pytest.raises(RuntimeError, match="[Pp]allas"):
            _simulate(sess, wfs, cfgs)


def test_sim_engine_validation():
    assert set(SIM_ENGINES) == {"auto", "pallas", "xla"}
    with pytest.raises(ValueError):
        SweepEngine(sim_engine="mosaic")
    with pytest.raises(ValueError):
        SweepSession(sim_engine="mosaic")
    # the session knob re-points a borrowed engine
    eng = SweepEngine(sim_engine="xla")
    sess = SweepSession(engine=eng, sim_engine="pallas")
    assert eng.sim_engine == "pallas"
    assert sess.engine is eng


def test_pallas_supported_on_this_host():
    """CI runs every leg on CPU, where interpret mode must qualify —
    if this fails the whole differential tier above silently tested
    nothing but the fallback."""
    assert kernel_refusal() is None
    assert jax.default_backend() in ("cpu", "tpu")


# ---------------- f32 escape hatch (dtype-pinning regression) ---------------------

def test_sweep_f32_within_golden_rtol(monkeypatch):
    """With ``REPRO_SIM_X64=0`` the whole sim stack runs f32 (the only
    option on f64-less accelerators). Bit-faithful FIFO tie-breaking is
    out the window, but scan must still track exact within the golden
    fixture tolerance — this catches any construction site that pins
    f64 literals instead of canonicalizing (a mixed-dtype batch shows
    up as a large scan/exact gap here)."""
    monkeypatch.setenv("REPRO_SIM_X64", "0")
    wf = to_workflow(load_trace(TRACES / "montage_small.json"))
    cfg = grid(n_nodes=[9], chunk_sizes=[MB],
               partitions=[(4, 4)])[0].to_config()
    pred = Predictor(ST, session=SweepSession())
    exact = pred.predict(wf, cfg, backend="exact").makespan
    scan = pred.predict(wf, cfg, backend="scan").makespan
    assert scan == pytest.approx(exact, rel=FIXTURE_SCAN_EXACT_RTOL), (
        f"f32 scan drifted {abs(scan - exact) / exact:.2%} from exact "
        f"(golden bound {FIXTURE_SCAN_EXACT_RTOL:.1%})")
