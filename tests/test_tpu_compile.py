"""Ahead-of-time compiles of the sweep engine's executables for a TPU
v5e that is described, not attached, plus the engine's platform choice
of scan body on TPU.

The compiles run the chip's own compiler at the paper-scale bucket
(C=8 candidates x N=262144 op rows, R=128 resources, f64): a refusal or
a program that does not fit the chip's 16 GiB of HBM fails here at no
chip time. The topology is described inside a module-scoped fixture,
never while a module is imported: only one process may load the TPU
library at a time, and every test worker imports every test file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.core import MB, PAPER_RAMDISK, SweepSession, grid
from repro.core.compile import MAXD
from repro.core.jax_sim import FaultArrays, OpArrays
from repro.core.sweep import engine as engine_mod
from repro.core.sweep.shard import SHARD_AXIS
from repro.core.trace import load_trace, to_workflow
from repro.core.x64 import enable_x64

from test_sweep_kernel import TRACES

C, N, R = 8, 262144, 128
HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # describing the topology loads the TPU library, which otherwise
    # writes its logs to a fixed directory outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def bucket_args(sharding, faulted: bool):
    """Shapes of one (C, N, R) bucket batch, as `SweepEngine` stacks it."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    f = jnp.float64
    ops = OpArrays(res=s((C, N), jnp.int32), cls=s((C, N), jnp.int32),
                   nbytes=s((C, N), f), reqs=s((C, N), f),
                   extra=s((C, N), f), nlat=s((C, N), f),
                   deps=s((C, N, MAXD), jnp.int32))
    args = [ops, s((C, 7), f)]
    if faulted:
        args.append(FaultArrays(res_mult=s((C, R), f), dead=s((C, N), f)))
    return args


@pytest.mark.parametrize("exact", [False, True], ids=["scan", "exact"])
@pytest.mark.parametrize("faulted", [False, True],
                         ids=["healthy", "faulted"])
def test_engine_executable_compiles_for_v5e(one_chip, no_persistent_cache,
                                            exact, faulted):
    with enable_x64():
        fn = engine_mod._make_executable(R, exact=exact, faulted=faulted)
        compiled = fn.lower(*bucket_args(one_chip, faulted)).compile()
    mem = compiled.memory_analysis()
    used = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert 0 < used <= HBM_BYTES, f"{used / 2 ** 30:.2f} GiB"
    assert "tpu_custom_call" not in compiled.as_text()


def test_sharded_scan_executable_compiles_for_four_chips(
        topo, no_persistent_cache):
    mesh = Mesh(np.asarray(topo.devices[:4]), (SHARD_AXIS,))
    rows = NamedSharding(mesh, PartitionSpec(SHARD_AXIS))
    with enable_x64():
        fn = engine_mod._make_executable(R, exact=False, mesh=mesh)
        compiled = fn.lower(*bucket_args(rows, faulted=False)).compile()
    mem = compiled.memory_analysis()
    per_device = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert 0 < per_device <= HBM_BYTES
    # rows are independent: the partitioned program needs no collective
    assert "all-gather" not in compiled.as_text()


def test_tpu_scan_body_is_xla_and_pallas_refuses(monkeypatch):
    """On TPU the engine builds scan executables on XLA without counting
    a fallback, and ``sim_engine="pallas"`` raises with the compiler's
    reason. Steered by the platform query alone; runs on the CPU."""
    wf = to_workflow(load_trace(TRACES / "montage_small.json"))
    cands = grid(n_nodes=[7], chunk_sizes=[512 * 1024, 1 * MB])
    wfs, cfgs = [wf] * len(cands), [c.to_config() for c in cands]
    with SweepSession(sim_engine="xla") as sx:
        want = np.asarray(sx.simulate_batch(wfs, cfgs, st=PAPER_RAMDISK))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with SweepSession(sim_engine="auto") as sa:
        got = np.asarray(sa.simulate_batch(wfs, cfgs, st=PAPER_RAMDISK))
        assert sa.stats.kernel_buckets == 0
        assert sa.stats.kernel_fallbacks == 0
        assert not any(k[6] for k in sa.engine.cache_keys())
    np.testing.assert_array_equal(got, want)
    with SweepSession(sim_engine="pallas") as sp:
        with pytest.raises(RuntimeError, match="Mosaic refuses"):
            sp.simulate_batch(wfs, cfgs, st=PAPER_RAMDISK)
