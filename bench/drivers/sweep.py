"""One operator at `explore` on one `SweepSession`, one request at a
time. In a closed loop the window closes at the first request boundary
after ``seconds``; in an open loop requests due while one runs wait in
line, none is sent after ``seconds``, and the window closes when the
last has run."""
from __future__ import annotations

import time

from bench.drivers._base import Base, Outcome, freeze, program_st, ranked


class Driver(Base):
    def _explore(self, sess, req):
        import jax
        from repro.core import explore
        cands, prog = self.question(req)
        by_cand = dict(zip(cands, prog))
        with jax.profiler.TraceAnnotation("bench|explore"):
            return explore(by_cand.__getitem__, cands, program_st(req.st),
                           verify_top_k=req.verify_top_k, session=sess)

    def run(self, seconds: float) -> None:
        sess = self.session()
        try:
            for req in self.gen.warmup():
                self._explore(sess, req)
            freeze()
            self.setup_done = time.perf_counter()
            self.hooks.window_open()
            t0 = time.perf_counter()
            while True:
                due, req = self.gen.next()
                if due is not None:
                    if due >= seconds:
                        break
                    time.sleep(max(t0 + due - time.perf_counter(), 0.0))
                o = Outcome(req, time.perf_counter() if due is None
                            else t0 + due)
                o.sent = time.perf_counter()
                try:
                    o.ranked = ranked(self._explore(sess, req))
                except Exception as exc:      # counted as failed
                    o.error = repr(exc)
                o.end = time.perf_counter()
                self.outcomes.append(o)
                if due is None and o.end - t0 >= seconds:
                    break
            self.window = (t0, time.perf_counter())
            self.hooks.window_close()
        finally:
            sess.close()
