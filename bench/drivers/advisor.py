"""Requests against one `AdvisorServer`. In an open loop each is sent
when it is due, whether or not earlier answers came back, none after
``seconds``, and the window closes when every answer has come; in a
closed loop each is sent when the last answer came, until ``seconds``
have passed."""
from __future__ import annotations

import asyncio
import time

from bench.drivers._base import Base, Outcome, freeze, program_st, ranked


class Driver(Base):
    def _request(self, req):
        from repro.serve import AdvisorRequest
        cands, prog = self.question(req)
        return AdvisorRequest(workflow=prog[0], candidates=cands,
                              verify_top_k=req.verify_top_k)

    async def _main(self, seconds: float) -> None:
        from repro.serve import AdvisorServer
        from bench.generator import service_times
        cfg = self.cell.config
        st = service_times(cfg["service_times"][cfg["service_profile"]])
        sess = self.session()
        try:
            await self._serve(AdvisorServer(program_st(st), session=sess),
                              seconds)
        finally:
            sess.close()

    async def _serve(self, server, seconds: float):
        async with server as srv:
            for req in self.gen.warmup():
                await srv.submit(self._request(req))
            freeze()
            self.setup_done = time.perf_counter()
            self.hooks.window_open()
            t0 = time.perf_counter()
            tasks = []

            async def one(o, areq):
                try:
                    resp = await srv.submit(areq)
                    o.ranked = ranked(resp.evaluations)
                except Exception as exc:      # counted as failed
                    o.error = repr(exc)
                o.end = time.perf_counter()

            lag = asyncio.ensure_future(self._watch_loop())
            while True:
                due, req = self.gen.next()
                if due is None:
                    if time.perf_counter() - t0 >= seconds:
                        break
                elif due >= seconds:
                    break
                areq = self._request(req)
                if due is not None:
                    wait = t0 + due - time.perf_counter()
                    if wait > 0:
                        await asyncio.sleep(wait)
                o = Outcome(req, time.perf_counter() if due is None
                            else t0 + due)
                o.sent = time.perf_counter()
                self.outcomes.append(o)
                tasks.append(asyncio.ensure_future(one(o, areq)))
                if due is None:
                    await tasks[-1]
            await asyncio.gather(*tasks)
            self.window = (t0, time.perf_counter())
            self.hooks.window_close()
            lag.cancel()

    async def _watch_loop(self, tick: float = 0.01) -> None:
        """How late the event loop wakes: the longest stall, and the
        seconds it spent more than 0.1 s late."""
        while True:
            t = time.perf_counter()
            await asyncio.sleep(tick)
            late = time.perf_counter() - t - tick
            self.loop_lag_max = max(self.loop_lag_max, late)
            if late > 0.1:
                self.loop_lag_over += late

    def run(self, seconds: float) -> None:
        self.loop_lag_max = self.loop_lag_over = 0.0
        asyncio.run(self._main(seconds))
