"""The plain reference against the system's own simulators, on the CPU
at small sizes: bit-equal DAGs, scan and exact makespans."""
import numpy as np
import pytest

from bench import workflows as W
from bench.generator import service_times
from bench.reference.compile import compile_dag
from bench.reference.sim import exact_makespan, scan_makespan

MB = W.MB
RAMDISK = {"net_remote_Bps": 124780544, "net_local_Bps": 2362232012.8,
           "net_latency_s": 0.0001, "storage_Bps": 1181116006.4,
           "manager_s": 0.0004, "client_s": 0.0, "storage_req_s": 0.0003}


def fixtures():
    lays = [W.layout(3, 3, chunk_size=1 * MB, stripe_width=0, replication=1),
            W.layout(4, 2, chunk_size=4 * MB, stripe_width=1, replication=2)]
    wfs = [W.blast(3, n_queries=7, db_bytes=6 * MB, per_query_s=2.0,
                   query_bytes=MB, out_bytes=2 * MB),
           W.reduce_(3, in_bytes=3 * MB, mid_bytes=2 * MB, out_bytes=5 * MB,
                     wass=False),
           W.reduce_(3, in_bytes=3 * MB, mid_bytes=2 * MB, out_bytes=5 * MB,
                     wass=True),
           W.broadcast(3, file_bytes=5 * MB - 7, out_bytes=MB,
                       replication=2),
           W.stripe(3, file_bytes=2 * MB, n_hot=3, out_bytes=MB)]
    return [(wf, lay) for wf in wfs for lay in lays]


@pytest.mark.parametrize("case", range(10))
def test_reference_is_bit_equal_to_the_engine(case):
    from repro.core import SweepEngine, ServiceTimes, compile_workflow
    wf, lay = fixtures()[case]
    st = service_times(RAMDISK)
    ops = compile_workflow(W.to_program(wf), W.to_candidate(lay, 7).to_config())
    dag = compile_dag(wf, lay)
    assert np.array_equal(ops.res, dag["res"])
    assert np.array_equal(ops.deps, np.asarray(dag["deps"]))
    assert np.array_equal(ops.nbytes, dag["nbytes"])
    assert ops.n_resources == dag["n_resources"]
    eng = SweepEngine(sim_engine="xla")
    pst = ServiceTimes(**st)
    scan = eng.simulate_batch([ops], [pst])[0]
    exact = eng.simulate_batch([ops], [pst], exact=True)[0]
    assert scan == scan_makespan(dag, st)
    assert exact == exact_makespan(dag, st)
