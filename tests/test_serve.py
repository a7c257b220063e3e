"""Advisor-service tests (repro.serve).

The server's contract is bit-identity: whatever batching, coalescing,
or caching happens between admission and response, every client's
evaluations are element-wise identical to a direct per-request
`explore()` on a fresh session. On top sit the serving counters:
coalesced compiles strictly below the request count, ZERO compiles and
zero simulator batches on a results-cache hit, lazy invalidation when
the service digest changes (re-identified system), and deadlines —
measured from submit, the fixed `item_timeout_s` semantics — that fail
cleanly without wedging the dispatcher.
"""
import asyncio
import dataclasses

import numpy as np
import pytest

from repro.core import (MB, PAPER_RAMDISK, CompileCache, Predictor,
                        SweepEngine, SweepSession, explore, grid)
from repro.core import workloads as W
from repro.core.compile import compile_count
from repro.obs import Tracer
from repro.serve import (AdvisorRequest, AdvisorServer, DeadlineExceeded,
                         ServerClosed, service_digest)

ST = PAPER_RAMDISK


def serve_grid():
    # a fixed workflow's client ranks must fit every candidate: pin the
    # partitions so n_app >= 2 for the 2-client blast workflows below
    return grid(n_nodes=[7], partitions=[(2, 4)],
                chunk_sizes=[512 * 1024, 1 * MB])


def wf_a():
    return W.blast(2, n_queries=8, db_mb=16, per_query_s=1.0)


def wf_b():
    return W.blast(2, n_queries=10, db_mb=16, per_query_s=1.0)


def direct(wf, st=ST, verify_top_k=3):
    """The bit-identity reference: a per-request explore on fresh state."""
    evals = explore(lambda c: wf, serve_grid(), st,
                    verify_top_k=verify_top_k, engine=SweepEngine(),
                    compile_cache=CompileCache())
    return np.asarray([e.makespan for e in evals])


def req(wf, **kw):
    kw.setdefault("verify_top_k", 3)
    return AdvisorRequest(workflow=wf, candidates=serve_grid(), **kw)


def test_coalescing_cache_and_invalidation():
    base_a, base_b = direct(wf_a()), direct(wf_b())

    async def main():
        # 8 concurrent clients, 2 distinct structural questions
        reqs = [req(wf_a() if i % 2 == 0 else wf_b(), client=f"c{i}")
                for i in range(8)]
        async with AdvisorServer(ST, batch_window_s=0.25) as srv:
            n0 = compile_count()
            resps = await asyncio.gather(*(srv.submit(r) for r in reqs))
            compiles = compile_count() - n0
            for i, r in enumerate(resps):
                np.testing.assert_array_equal(
                    r.makespans, base_a if i % 2 == 0 else base_b)
            assert 0 < compiles < len(reqs)     # coalesced: strictly fewer
            assert srv.stats.sweeps == 2        # one explore per question
            assert srv.stats.coalesced == len(reqs) - 2
            assert not any(r.cached for r in resps)

            # repeat queries: results-cache hits — zero compiles, zero
            # simulator batches, answers unchanged
            n1, b1 = compile_count(), srv.session.stats.batch_calls
            again = await asyncio.gather(srv.submit(reqs[0]),
                                         srv.submit(reqs[1]))
            assert all(r.cached for r in again)
            np.testing.assert_array_equal(again[0].makespans, base_a)
            np.testing.assert_array_equal(again[1].makespans, base_b)
            assert compile_count() == n1
            assert srv.session.stats.batch_calls == b1
            assert srv.results.stats.hits == 2

            # a re-identified system: stale answers invalidate lazily on
            # next lookup (digest mismatch), never get served
            st2 = ST.replace(storage=ST.storage * 2.0)
            assert service_digest(st2) != service_digest(ST)
            srv.set_service_times(st2)
            r2 = await srv.submit(reqs[0])
            assert not r2.cached
            assert srv.results.stats.invalidations == 1
            np.testing.assert_array_equal(r2.makespans, direct(wf_a(), st2))

    asyncio.run(main())


def test_deadline_expired_fails_cleanly():
    async def main():
        async with AdvisorServer(ST, batch_window_s=0.02) as srv:
            with pytest.raises(DeadlineExceeded):
                await srv.submit(req(wf_a(), verify_top_k=1, timeout_s=0.0))
            assert srv.stats.deadline_expired == 1
            assert srv.stats.sweeps == 0        # never occupied a sweep
            # the dispatcher survives: the next request is served
            ok = await srv.submit(req(wf_a(), verify_top_k=1))
            assert ok.makespans.size == len(serve_grid())
            np.testing.assert_array_equal(
                ok.makespans, direct(wf_a(), verify_top_k=1))

    asyncio.run(main())


def test_from_predictor_shares_warm_session():
    pred = Predictor(ST)

    async def main():
        async with AdvisorServer.from_predictor(pred) as srv:
            assert srv.session is pred.sweep_session()
            r = await srv.submit(req(wf_a(), verify_top_k=1))
            np.testing.assert_array_equal(
                r.makespans, direct(wf_a(), verify_top_k=1))

    asyncio.run(main())
    # closing the server must not close a session it does not own
    assert not pred.sweep_session().closed


def test_lifecycle_guards():
    async def main():
        srv = AdvisorServer(ST)
        with pytest.raises(ServerClosed):       # not started
            await srv.submit(req(wf_a()))
        await srv.start()
        await srv.close()
        with pytest.raises(ServerClosed):       # closed
            await srv.submit(req(wf_a()))
        await srv.close()                       # idempotent
        assert srv.session.closed               # owned session torn down

    asyncio.run(main())


def test_request_validation():
    with pytest.raises(ValueError):
        AdvisorRequest(workflow=wf_a(), candidates=())
    with pytest.raises(ValueError):
        AdvisorRequest(workflow=wf_a(), candidates=serve_grid(),
                       objective="latency")


def test_query_key_is_structural():
    # structurally-equal questions coalesce; any knob change separates
    a1, a2 = req(wf_a()), req(wf_a(), client="other")
    assert a1.query_key() == a2.query_key()     # client tag never keys
    assert a1.query_key() != req(wf_b()).query_key()
    assert a1.query_key() != req(wf_a(), verify_top_k=1).query_key()
    assert a1.query_key() != \
        req(wf_a(), locality_aware=False).query_key()


# ---------------- serve spans ------------------------------------------------------

def _named(tr, name, phase):
    return [s for s in tr.spans() if (s.name, s.phase) == (name, phase)]


def _check_ticket_spans(tr, n_tickets):
    """One ``request|serve`` and one ``queued|serve-queue`` span per
    admitted ticket, paired by ``req``, the queue wait inside the
    request."""
    reqs = {dict(s.meta)["req"]: s for s in _named(tr, "request", "serve")}
    queued = {dict(s.meta)["req"]: s
              for s in _named(tr, "queued", "serve-queue")}
    assert sorted(reqs) == sorted(queued) == list(range(n_tickets))
    assert len(_named(tr, "request", "serve")) == n_tickets
    assert len(_named(tr, "queued", "serve-queue")) == n_tickets
    for i, r in reqs.items():
        assert r.start <= queued[i].start and queued[i].end <= r.end


async def _answered(srv):
    await srv.submit(req(wf_a(), verify_top_k=1))
    return 1, [((0,), False)]


async def _coalesced(srv):
    await asyncio.gather(*(srv.submit(req(wf_a(), verify_top_k=1))
                           for _ in range(3)))
    return 3, [((0, 1, 2), False)]


async def _cached(srv):
    await srv.submit(req(wf_a(), verify_top_k=1))
    r = await srv.submit(req(wf_a(), verify_top_k=1))
    assert r.cached
    return 2, [((0,), False), ((1,), True)]


async def _expired(srv):
    with pytest.raises(DeadlineExceeded):
        await srv.submit(req(wf_a(), verify_top_k=1, timeout_s=0.0))
    return 1, []


async def _closed(srv):
    # both tickets are taken into a batch whose window is still open
    # when the server closes: each fails, and no span stays open
    tasks = [asyncio.ensure_future(srv.submit(req(w(), verify_top_k=1)))
             for w in (wf_a, wf_b)]
    await asyncio.sleep(0.2)
    await srv.close()
    for t in tasks:
        with pytest.raises(ServerClosed):
            await asyncio.wait_for(t, timeout=10.0)
    return 2, []


@pytest.mark.parametrize("path", [_answered, _coalesced, _cached, _expired,
                                  _closed], ids=lambda f: f.__name__[1:])
def test_every_ticket_yields_request_and_queued_spans(path):
    tr = Tracer()
    window = 30.0 if path is _closed else 0.25

    async def main():
        with SweepSession(tracer=tr) as sess:
            async with AdvisorServer(ST, session=sess,
                                     batch_window_s=window) as srv:
                return await path(srv)

    n_tickets, groups = asyncio.run(main())
    _check_ticket_spans(tr, n_tickets)
    dispatch = _named(tr, "dispatch", "serve")
    got = sorted((dict(s.meta)["reqs"], dict(s.meta)["cached"])
                 for s in dispatch)
    assert got == sorted(groups)
    for s in dispatch:
        assert dict(s.meta)["group"] == len(dict(s.meta)["reqs"])


def test_tracer_off_server_is_bit_identical_with_equal_counters():
    """The server differential: a live tracer changes no answer and no
    serving, results-cache, engine or DAG-cache counter."""

    async def drive(tracer):
        with SweepSession(tracer=tracer) as sess:
            async with AdvisorServer(ST, session=sess,
                                     batch_window_s=0.25) as srv:
                first = await asyncio.gather(*(
                    srv.submit(req(wf_a() if i % 2 else wf_b(),
                                   verify_top_k=2)) for i in range(4)))
                again = await srv.submit(req(wf_a(), verify_top_k=2))
                with pytest.raises(DeadlineExceeded):
                    await srv.submit(req(wf_a(), timeout_s=0.0))
                return ([r.makespans for r in first + [again]],
                        dataclasses.asdict(srv.stats),
                        dataclasses.asdict(srv.results.stats),
                        dataclasses.asdict(sess.stats),
                        dataclasses.asdict(sess.compile_stats))

    on = asyncio.run(drive(Tracer()))
    off = asyncio.run(drive(None))
    for a, b in zip(on[0], off[0]):
        np.testing.assert_array_equal(a, b)
    assert on[1:] == off[1:]
