"""Drive the storage predictor's served path once on one TPU chip, at the
paper's BLAST deployment size, and check what comes out.

    python chip_smoke.py                # phases 0-3 on one chip
    python chip_smoke.py --four-chips   # only: the phase-1 grid on
                                        # ShardedBackend(4) vs one device
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
                                        # CPU rehearsal, tiny database

Phases (one process; it owns the chip):

0. device — a TPU, or exit non-zero; never carry on on the CPU.
1. sweep — the advisor's Scenario I as `provisioning_advisor.py --nodes
   20 --workload blast` runs it: BLAST with 100 queries against the
   paper's 1,710 MB database (arXiv 1302.4760 §3.2) on a 20-node
   cluster, 54 candidates (every app/storage split x chunks 256 KB, 1 MB,
   4 MB), through `explore` on one `SweepSession`, cold then warm.
2. faults — one `disk=0:8,kill=1@40` scenario crossed with replication
   1 and 2 into the 1 MB-chunk slice of the same grid.
3. serving — an `AdvisorServer` on the phase-1 session answers two
   questions, each asked three times at once, then the same round again.

Exact verification is O(N^2) per candidate and, on a v5e, takes ~82 ns
per op-row per while-loop iteration (N = 2^16: 354 s for one
candidate), so the smoke verifies only candidates whose op-count bucket
is at most `VERIFY_MAX_OPS`; the cut is printed. The last line of
standard output is one JSON object naming the device; a failed check
raises and the script exits non-zero.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from pathlib import Path

# the TPU library otherwise writes its logs to a fixed directory outside
# the checkout; set before JAX starts up
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

# the paper's BLAST scale (§3.2) and the rehearsal's stand-in
DB_MB = 1710
REHEARSAL_DB_MB = 8
N_QUERIES = 100
N_NODES = 20

# exact verification only for candidates whose op-count bucket is at
# most this (see the module docstring for the measured cost)
VERIFY_MAX_OPS = 2 ** 14

# v5e emulates f64 in software and does not round like IEEE f64:
# measured on the chip, exact-mode makespans differ from the host
# reference by up to 3.4e-14 relative, and scan makespans (sequential
# chains of ~4e5 adds) by up to 5.5e-12. The bound leaves room for
# chains of 1e6 ops; on the CPU the results must be bit-equal.
TPU_RTOL = 1e-9

# the scan/exact bound that the tests pin on the shipped trace fixtures
# (`FIXTURE_SCAN_EXACT_RTOL`, tests/test_trace.py). At BLAST §3.2 scale
# scan mode misses it on the host as on the chip (ROADMAP A5b), so the
# smoke reports the gap against it and does not check it.
FIXTURE_SCAN_EXACT_RTOL = 0.015

FAULT_SPEC = "disk=0:8,kill=1@40"


class Counters:
    """XLA backend compiles and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-300)


def host_scan_makespan(ops, st) -> float:
    """Scan-mode semantics in plain Python, independent of the device
    code: serve the ops in `scan_order`, each through its resource's
    FIFO queue; an op starts once its dependencies completed and its
    resource is free."""
    from repro.core import jax_sim, ref_sim
    perm = jax_sim.scan_order(ops, st)
    dur = ref_sim.durations(ops, st)
    lag = ops.nlat * st.net_latency
    avail = [0.0] * ops.n_resources
    end = [0.0] * ops.n_ops
    served = [False] * ops.n_ops
    makespan = 0.0
    for i in perm.tolist():
        ready = 0.0
        for d in ops.deps[i].tolist():
            # a dependency not yet served in scan order reads as 0.0,
            # as in the device scan
            if d >= 0 and served[d] and end[d] > ready:
                ready = end[d]
        r = int(ops.res[i])
        start = ready if ready > avail[r] else avail[r]
        fin = start + float(dur[i])
        avail[r] = fin
        end[i] = fin + float(lag[i])
        served[i] = True
        makespan = max(makespan, fin)
    return makespan


class Smoke:
    def __init__(self, platform: str, db_mb: int):
        from repro.core import PAPER_RAMDISK
        self.st = PAPER_RAMDISK
        self.db_mb = db_mb
        self.rtol = TPU_RTOL if platform == "tpu" else 0.0
        self.on_tpu = platform == "tpu"
        self.counters = Counters()
        self.worst = {"exact": 0.0, "scan": 0.0}

    def workflow_for(self, c):
        from repro.core import workloads as W
        return W.blast(c.n_app, n_queries=N_QUERIES, db_mb=self.db_mb)

    def agree(self, kind: str, got: float, want: float, what: str) -> None:
        d = rel_diff(got, want)
        self.worst[kind] = max(self.worst[kind], d)
        check(d <= self.rtol, f"{what}: device {got!r} vs host {want!r} "
                              f"(rel {d:.3g} > {self.rtol:g})")

    def bucket(self, sess, c) -> int:
        from repro.core.sweep.buckets import bucket_of
        ops = sess.compile_cache.get(self.workflow_for(c), c.to_config())
        return bucket_of(ops)[0]

    def sweep(self, sess, cands, faults=None):
        """The full grid in scan mode, then `explore` with
        ``verify_top_k=3`` over the candidates at most `VERIFY_MAX_OPS`
        ops (their scan executables are the full sweep's: same buckets,
        same batch buckets). Returns (all evaluations, verified run,
        candidates cut from verification, seconds of the scan run,
        seconds of the verified run)."""
        from repro.core import explore, with_faults
        if faults is not None:
            cands = with_faults(cands, faults)
        t0 = time.monotonic()
        scan = explore(self.workflow_for, cands, self.st, verify_top_k=0,
                       session=sess)
        t1 = time.monotonic()
        small = [c for c in cands if self.bucket(sess, c) <= VERIFY_MAX_OPS]
        check(len(small) >= 1, "no candidate small enough to verify")
        t2 = time.monotonic()
        verified = explore(self.workflow_for, small, self.st,
                           verify_top_k=3, session=sess)
        return (scan, verified, len(cands) - len(small), t1 - t0,
                time.monotonic() - t2)

    def check_rows(self, sess, scan_evals, verified_evals, label: str):
        """Verified rows against `ref_sim`; the scan makespan of every
        verified row and of one row per op-count bucket against the
        host scan."""
        from repro.core import ref_sim
        from repro.core.sweep.buckets import bucket_of
        rows = [e for e in verified_evals if e.verified]
        check(len(rows) >= 1, f"{label}: nothing was verified")
        gaps = []
        for e in rows:
            c = e.candidate
            ops = sess.compile_cache.get(self.workflow_for(c), c.to_config())
            ref = ref_sim.simulate(ops, self.st).makespan
            self.agree("exact", e.makespan, ref, f"{label} exact {c}")
            self.agree("scan", e.scan_makespan, host_scan_makespan(ops, self.st),
                       f"{label} scan {c}")
            gaps.append(rel_diff(e.scan_makespan, ref))
        seen = set()
        for e in scan_evals:
            c = e.candidate
            ops = sess.compile_cache.get(self.workflow_for(c), c.to_config())
            b = bucket_of(ops)
            if b in seen:
                continue
            seen.add(b)
            self.agree("scan", e.scan_makespan, host_scan_makespan(ops, self.st),
                       f"{label} scan {c}")
        return rows, gaps, sorted(seen)

    @staticmethod
    def report_gap(label: str, gaps) -> None:
        over = [g for g in gaps if g > FIXTURE_SCAN_EXACT_RTOL]
        print(f"{label} known fault, not checked: scan/exact gap above "
              f"FIXTURE_SCAN_EXACT_RTOL={FIXTURE_SCAN_EXACT_RTOL:g} on "
              f"{len(over)} of {len(gaps)} verified rows (worst "
              f"{max(gaps):.4%}; ROADMAP A5b)", flush=True)

    # -- phases ----------------------------------------------------------------
    def phase_sweep(self, sess):
        import numpy as np
        from repro.core import MB, grid
        cands = grid(n_nodes=[N_NODES], chunk_sizes=[256 * 1024, 1 * MB, 4 * MB])
        check(len(cands) == 54, f"Scenario I grid has {len(cands)} candidates")
        runs = {}
        for label in ("cold", "warm"):
            s = sess.stats
            m0, sims0 = s.misses, s.sims
            c0, t0 = self.counters.compile_s, time.monotonic()
            scan, verified, cut, scan_s, verify_s = self.sweep(sess, cands)
            wall = time.monotonic() - t0
            runs[label] = (scan, verified)
            print(f"phase 1 sweep {label}: wall={wall:.2f}s "
                  f"scan_explore={scan_s:.2f}s verify_explore={verify_s:.2f}s "
                  f"xla_compiles={s.misses - m0} "
                  f"backend_compile_s={self.counters.compile_s - c0:.2f} "
                  f"misses={s.misses} kernel_buckets={s.kernel_buckets} "
                  f"kernel_fallbacks={s.kernel_fallbacks} "
                  f"padded_rows={s.padded_rows} sims={s.sims - sims0}",
                  flush=True)
            if label == "warm":
                check(s.misses == m0, f"warm sweep compiled {s.misses - m0}")
        cold_scan, cold_ver = runs["cold"]
        warm_scan, warm_ver = runs["warm"]
        check(len(cold_scan) == 54, "the sweep lost candidates")
        check(np.array_equal([e.makespan for e in cold_scan],
                             [e.makespan for e in warm_scan])
              and np.array_equal([e.makespan for e in cold_ver],
                                 [e.makespan for e in warm_ver]),
              "warm sweep answered differently from cold")
        if self.on_tpu:
            check(sess.stats.kernel_buckets == 0
                  and sess.stats.kernel_fallbacks == 0,
                  "a Pallas kernel executable was built on the TPU")
        rows, gaps, buckets = self.check_rows(sess, cold_scan, cold_ver,
                                              "phase 1")
        best = cold_scan[0].candidate
        print(f"phase 1 cut: verify_top_k=3 over the {len(cold_ver)} of 54 "
              f"candidates at op-count bucket <= {VERIFY_MAX_OPS}; the "
              f"other {cut} are ranked by scan only", flush=True)
        print(f"phase 1 scan best: {best.n_app} app / {best.n_storage} "
              f"storage, {best.chunk_size >> 10} KB -> "
              f"{cold_scan[0].scan_makespan!r}s; buckets {buckets}",
              flush=True)
        for e, g in zip(rows, gaps):
            c = e.candidate
            print(f"phase 1 verified: {c.n_app} app, {c.chunk_size >> 10} KB "
                  f"exact={e.makespan!r} scan={e.scan_makespan!r} "
                  f"scan/exact gap={g:.4%}", flush=True)
        self.report_gap("phase 1", gaps)

    def phase_faults(self, sess):
        from repro.core import MB, grid, parse_faults
        scen = parse_faults(FAULT_SPEC)
        cands = grid(n_nodes=[N_NODES], chunk_sizes=[1 * MB],
                     replications=[1, 2])
        m0, t0 = sess.stats.misses, time.monotonic()
        scan, verified, cut, scan_s, verify_s = self.sweep(
            sess, cands, faults=[scen])
        faulted = [k for k in sess.engine.cache_keys() if k[5]]
        check(len(faulted) >= 2, "no faulted executable was built")
        check(any(k[3] for k in faulted), "no faulted exact executable")
        rows, gaps, _ = self.check_rows(sess, scan, verified, "phase 2")
        print(f"phase 2 faults: wall={time.monotonic() - t0:.2f}s "
              f"scan_explore={scan_s:.2f}s verify_explore={verify_s:.2f}s "
              f"candidates={len(scan)} verified={len(rows)} cut={cut} "
              f"xla_compiles={sess.stats.misses - m0} "
              f"faulted_executables={len(faulted)} "
              f"failed={sum(e.failed for e in scan)}", flush=True)
        self.report_gap("phase 2", gaps)

    def phase_serve(self, sess):
        import numpy as np
        from repro.core import MB, explore, grid
        from repro.core import workloads as W
        from repro.serve import AdvisorRequest, AdvisorServer

        def question(n_app):
            wf = W.blast(n_app, n_queries=N_QUERIES, db_mb=self.db_mb)
            cands = grid(n_nodes=[N_NODES],
                         partitions=[(n_app, N_NODES - 1 - n_app)],
                         chunk_sizes=[4 * MB], replications=[1, 2])
            return AdvisorRequest(workflow=wf, candidates=cands,
                                  verify_top_k=1)

        questions = [question(2), question(3)]

        async def serve():
            async with AdvisorServer(self.st, session=sess) as srv:
                reqs = [q for q in questions for _ in range(3)]
                first = await asyncio.gather(*(srv.submit(r) for r in reqs))
                sweeps = srv.stats.sweeps
                sims0 = sess.stats.sims
                again = await asyncio.gather(*(srv.submit(r) for r in reqs))
                return reqs, first, again, sweeps, sess.stats.sims - sims0, \
                    srv.stats

        t0 = time.monotonic()
        reqs, first, again, sweeps, repeat_sims, st = asyncio.run(serve())
        check(len(reqs) >= 6, "fewer than six requests")
        check(sweeps < len(reqs), f"no coalescing: {sweeps} sweeps for "
                                  f"{len(reqs)} requests")
        check(repeat_sims == 0 and all(r.cached for r in again),
              f"repeat round ran {repeat_sims} sims")
        for q in questions:
            direct = explore(lambda c, wf=q.workflow: wf, list(q.candidates),
                             self.st, verify_top_k=q.verify_top_k,
                             session=sess)
            want = np.asarray([e.makespan for e in direct])
            for r, resp in zip(reqs + reqs, first + again):
                if r is q:
                    check(np.array_equal(resp.makespans, want),
                          "served answer differs from a direct explore")
        print(f"phase 3 serve: wall={time.monotonic() - t0:.2f}s "
              f"requests={st.requests} sweeps={st.sweeps} "
              f"coalesced={st.coalesced} repeat_round_sims={repeat_sims}",
              flush=True)


def four_chips(smoke: Smoke) -> None:
    """The phase-1 grid in scan mode on `ShardedBackend(4)` against the
    same grid on one device: makespans must be array-equal. Each session
    has its own DAG cache, so each wall time includes its host compile."""
    import numpy as np
    from repro.core import MB, ShardedBackend, SweepSession, explore, grid
    cands = grid(n_nodes=[N_NODES], chunk_sizes=[256 * 1024, 1 * MB, 4 * MB])
    out = {}
    for name, backend in (("one device", None), ("sharded", ShardedBackend(4))):
        with SweepSession(backend) as sess:
            c0, t0 = smoke.counters.compile_s, time.monotonic()
            ev = explore(smoke.workflow_for, cands, smoke.st,
                         verify_top_k=0, session=sess)
            out[name] = np.asarray(
                [m for _, m in sorted((e.index, e.makespan) for e in ev)])
            print(f"four-chips {name}: wall={time.monotonic() - t0:.2f}s "
                  f"xla_compiles={sess.stats.misses} "
                  f"backend_compile_s={smoke.counters.compile_s - c0:.2f} "
                  f"sharded_batch_calls={sess.stats.sharded_batch_calls}",
                  flush=True)
            rows = sess.stats.device_rows
    check(len(rows) == 4, f"rows landed on {len(rows)} devices")
    print("four-chips device_rows: "
          + ", ".join(f"{d}={n}" for d, n in sorted(rows.items())),
          flush=True)
    check(np.array_equal(out["one device"], out["sharded"]),
          "sharded makespans differ from one device")
    print(f"four-chips: {len(cands)} makespans array-equal", flush=True)


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the sharded-vs-one-device comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow the CPU and use a tiny database")
    args = ap.parse_args(argv)

    import jax
    t_start = time.monotonic()
    devices = jax.devices()
    dev = devices[0]
    print(f"phase 0 device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    if dev.platform != "tpu" and not args.rehearse:
        print("phase 0: no TPU found; refusing to run on "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"--four-chips needs 4 devices, found {len(devices)}",
              file=sys.stderr)
        return 2

    smoke = Smoke(dev.platform, REHEARSAL_DB_MB if args.rehearse else DB_MB)
    if args.four_chips:
        four_chips(smoke)
    else:
        from repro.core import SweepSession
        print(f"config: BLAST db_mb={smoke.db_mb} queries={N_QUERIES} "
              f"nodes={N_NODES} verify_max_ops={VERIFY_MAX_OPS} "
              f"rtol={smoke.rtol:g}", flush=True)
        with SweepSession() as sess:
            smoke.phase_sweep(sess)
            smoke.phase_faults(sess)
            smoke.phase_serve(sess)
        print(f"checks: worst device/host rel diff exact="
              f"{smoke.worst['exact']:.3g} scan={smoke.worst['scan']:.3g}",
              flush=True)
    c = smoke.counters
    print(f"compile: backend_compiles={c.compiles} "
          f"backend_compile_s={c.compile_s:.2f} "
          f"persistent_cache_hits={c.cache_hits} "
          f"total_wall={time.monotonic() - t_start:.2f}s", flush=True)
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
