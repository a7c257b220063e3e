"""Unit + property tests for the queue-model simulators.

The property tests run under hypothesis when it is installed and fall
back to a deterministic seeded generator (same workflow distribution)
when it is not, so the suite stays green on minimal environments.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as hst
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.core import (MB, PAPER_RAMDISK, Placement, ServiceTimes, Task,
                        Workflow, collocated_config, compile_workflow,
                        partitioned_config)
from repro.core import jax_sim, ref_sim
from repro.core import workloads as W

ST = PAPER_RAMDISK


def small_cfg(**kw):
    return collocated_config(5, chunk_size=256 * 1024, **kw)


WORKLOADS = {
    "pipeline": lambda: W.pipeline(4, stage_mb=(4, 8, 4, 1)),
    "pipeline_wass": lambda: W.pipeline(4, wass=True, stage_mb=(4, 8, 4, 1)),
    "reduce": lambda: W.reduce_(4, in_mb=4, mid_mb=4, out_mb=8),
    "reduce_wass": lambda: W.reduce_(4, wass=True, in_mb=4, mid_mb=4, out_mb=8),
    "broadcast": lambda: W.broadcast(4, file_mb=4, replication=2),
}


@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_exact_mode_matches_oracle(name):
    ops = compile_workflow(WORKLOADS[name](), small_cfg())
    r_ref = ref_sim.simulate(ops, ST)
    r_jax = jax_sim.simulate(ops, ST, exact=True)
    assert r_ref.makespan == pytest.approx(r_jax.makespan, rel=1e-9)
    for tid, t in r_ref.per_task_end.items():
        assert r_jax.per_task_end[tid] == pytest.approx(t, rel=1e-9)


@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_scan_mode_close_to_oracle(name):
    ops = compile_workflow(WORKLOADS[name](), small_cfg())
    r_ref = ref_sim.simulate(ops, ST)
    r_scan = jax_sim.simulate(ops, ST)
    # scan mode trades exact FIFO order for vmap-ability; <=10% at paper
    # scale, somewhat looser on tiny latency-dominated workloads
    assert r_scan.makespan == pytest.approx(r_ref.makespan, rel=0.20)


def test_makespan_respects_bandwidth_floor():
    """A client cannot push bytes faster than its NIC serializes them."""
    wf = W.pipeline(1, stage_mb=(16, 16, 16, 16))
    ops = compile_workflow(wf, small_cfg())
    r = ref_sim.simulate(ops, ST)
    total_write = 3 * 16 * MB
    assert r.makespan >= total_write * ST.net_remote


def test_more_replication_never_decreases_write_work():
    base = rep = None
    for r_level, out in [(1, "base"), (3, "rep")]:
        wf = W.broadcast(4, file_mb=8, replication=r_level)
        ops = compile_workflow(wf, small_cfg())
        rep_t = ref_sim.simulate(ops, ST)
        if out == "base":
            base = (rep_t.per_stage_end["produce"], rep_t.storage_used)
        else:
            rep = (rep_t.per_stage_end["produce"], rep_t.storage_used)
    assert rep[0] >= base[0]          # producing with replicas takes >= time
    assert rep[1] == base[1] + 2 * 8 * MB   # + 2 extra copies of the hot file


def test_zero_size_ops_do_not_touch_storage():
    wf = Workflow(tasks=[Task(tid=0, inputs=(), outputs=(("z", 0),), client=0)])
    ops = compile_workflow(wf, small_cfg())
    from repro.core.compile import CLS_STORAGE
    assert not (ops.cls == CLS_STORAGE).any()
    # but the write still pays its two manager requests
    from repro.core.compile import CLS_MANAGER
    assert (ops.cls == CLS_MANAGER).sum() == 2


def test_manager_request_counts():
    """Paper §2.4: a write makes 2 manager requests, a read 1."""
    from repro.core.compile import CLS_MANAGER
    wf = Workflow(tasks=[
        Task(tid=0, inputs=(), outputs=(("a", 1 * MB),), client=0),
        Task(tid=1, inputs=("a",), outputs=(("b", 1 * MB),), client=1),
    ])
    ops = compile_workflow(wf, small_cfg())
    # write a: 2, read a: 1, write b: 2
    assert (ops.cls == CLS_MANAGER).sum() == 5


def test_dag_is_topological_and_acyclic():
    ops = compile_workflow(W.reduce_(4), small_cfg())
    assert (ops.deps < np.arange(ops.n_ops)[:, None]).all()


def test_service_time_sweep_matches_single_runs():
    ops = compile_workflow(W.broadcast(4, file_mb=4), small_cfg())
    profiles = [ST, ST.replace(storage=ST.storage * 10),
                ST.replace(net_remote=ST.net_remote * 2)]
    swept = jax_sim.sweep_service_times(
        ops, np.stack([jax_sim.st_to_vec(p) for p in profiles]),
        st_ref=ST, exact=True)
    singles = [jax_sim.simulate(ops, p, exact=True).makespan for p in profiles]
    np.testing.assert_allclose(swept, singles, rtol=1e-9)


def test_batch_matches_individual():
    cfgs = [small_cfg(), collocated_config(5, chunk_size=1 * MB),
            partitioned_config(2, 2, chunk_size=256 * 1024)]
    ops_list = [compile_workflow(W.reduce_(2, in_mb=2, mid_mb=2, out_mb=2), c)
                for c in cfgs]
    batch = jax_sim.simulate_batch(ops_list, [ST] * 3, exact=True)
    for got, ops in zip(batch, ops_list):
        want = ref_sim.simulate(ops, ST).makespan
        assert got == pytest.approx(want, rel=1e-9)


# ---------------- property-based tests -----------------------------------------

def make_random_workflow(rng: np.random.Generator):
    """Deterministic analogue of the hypothesis strategy below (same
    distribution, seeded numpy draws)."""
    n_hosts = int(rng.integers(3, 7))
    n_tasks = int(rng.integers(1, 7))
    tasks = []
    files = []
    for tid in range(n_tasks):
        n_in = int(rng.integers(0, min(2, len(files)) + 1))
        ins = tuple(rng.permutation(files)[:n_in]) if files else ()
        out = f"f{tid}"
        size = int(rng.integers(0, 5)) * 512 * 1024
        runtime = float(rng.uniform(0, 2))
        tasks.append(Task(tid=tid, inputs=ins, outputs=((out, size),),
                          runtime=runtime))
        files.append(out)
    cfg = collocated_config(
        n_hosts,
        chunk_size=[128 * 1024, 512 * 1024][int(rng.integers(0, 2))],
        replication=int(rng.integers(1, 3)),
        placement=[Placement.ROUND_ROBIN, Placement.LOCAL][int(rng.integers(0, 2))])
    return Workflow(tasks=tasks, name="rand"), cfg


def check_exact_equals_oracle(wf, cfg):
    ops = compile_workflow(wf, cfg)
    r_ref = ref_sim.simulate(ops, ST)
    r_jax = jax_sim.simulate(ops, ST, exact=True)
    assert r_jax.makespan == pytest.approx(r_ref.makespan, rel=1e-9, abs=1e-12)


def check_slower_network_never_faster(wf, cfg, factor):
    ops = compile_workflow(wf, cfg)
    fast = ref_sim.simulate(ops, ST).makespan
    slow = ref_sim.simulate(
        ops, ST.replace(net_remote=ST.net_remote * factor,
                        net_local=ST.net_local * factor)).makespan
    assert slow >= fast - 1e-9


if HAVE_HYPOTHESIS:
    @hst.composite
    def random_workflow(draw):
        n_hosts = draw(hst.integers(3, 6))
        n_tasks = draw(hst.integers(1, 6))
        tasks = []
        files = []
        for tid in range(n_tasks):
            n_in = draw(hst.integers(0, min(2, len(files))))
            ins = tuple(draw(hst.permutations(files))[:n_in]) if files else ()
            out = f"f{tid}"
            size = draw(hst.integers(0, 4)) * 512 * 1024
            runtime = draw(hst.floats(0, 2))
            tasks.append(Task(tid=tid, inputs=ins, outputs=((out, size),),
                              runtime=runtime))
            files.append(out)
        cfg = collocated_config(
            n_hosts, chunk_size=draw(hst.sampled_from([128 * 1024, 512 * 1024])),
            replication=draw(hst.integers(1, 2)),
            placement=draw(hst.sampled_from([Placement.ROUND_ROBIN, Placement.LOCAL])))
        return Workflow(tasks=tasks, name="rand"), cfg

    @settings(max_examples=25, deadline=None)
    @given(random_workflow())
    def test_property_exact_equals_oracle(wf_cfg):
        check_exact_equals_oracle(*wf_cfg)

    @settings(max_examples=15, deadline=None)
    @given(random_workflow(), hst.floats(1.5, 4.0))
    def test_property_slower_network_never_faster(wf_cfg, factor):
        check_slower_network_never_faster(*wf_cfg, factor)
else:
    @pytest.mark.parametrize("seed", range(25))
    def test_property_exact_equals_oracle(seed):
        wf, cfg = make_random_workflow(np.random.default_rng(seed))
        check_exact_equals_oracle(wf, cfg)

    @pytest.mark.parametrize("seed", range(15))
    def test_property_slower_network_never_faster(seed):
        rng = np.random.default_rng(1000 + seed)
        wf, cfg = make_random_workflow(rng)
        check_slower_network_never_faster(wf, cfg, float(rng.uniform(1.5, 4.0)))


@pytest.mark.parametrize("runtime", [5e-324, 1e-310, 0.0, 1e-300])
def test_subnormal_runtime_matches_oracle(runtime):
    """XLA flushes subnormal floats to zero; the compiler does the same
    to task runtimes, so exact mode still equals the host reference."""
    wf = Workflow(tasks=[Task(tid=0, inputs=(), outputs=(("f0", 0),),
                              runtime=runtime)], name="subnormal")
    cfg = collocated_config(3, chunk_size=128 * 1024, replication=1,
                            placement=Placement.ROUND_ROBIN)
    check_exact_equals_oracle(wf, cfg)
