"""Benchmark harness: one entry per paper table/figure + the framework
roofline. Prints ``name,value,derived`` CSV (value is the benchmark's
primary metric: abs error %, spread x, seconds, or roofline fraction);
``--json PATH`` additionally writes the rows as a JSON document (the
machine-readable record CI uploads as an artifact per push, so the perf
trajectory is queryable across commits).

    PYTHONPATH=src python -m benchmarks.run [--only fig4,accuracy]
        [--json out.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def all_benchmarks():
    from . import accuracy, paper_figures, roofline, serve_bench, sweep_bench
    return {
        "sweepcache": sweep_bench.sweep_cache,
        "sweepcompile": sweep_bench.sweep_compile,
        "sweepfaults": sweep_bench.sweep_faults,
        "sweepkernel": sweep_bench.sweep_kernel,
        "sweepmp": sweep_bench.sweep_mp,
        "sweepobs": sweep_bench.sweep_obs,
        "sweepscenarios": sweep_bench.sweep_scenarios,
        "sweepserve": serve_bench.sweep_serve,
        "sweepshard": sweep_bench.sweep_shard,
        "sweeptrace": sweep_bench.sweep_trace,
        "fig1": paper_figures.fig1_stripe_sweep,
        "fig4": paper_figures.fig4_pipeline,
        "fig5": paper_figures.fig5_reduce,
        "fig6": paper_figures.fig6_broadcast,
        "fig8": paper_figures.fig8_scenario1,
        "fig9": paper_figures.fig9_scenario2,
        "speedup": paper_figures.speedup,
        "hdd": paper_figures.hdd_reduce,
        "accuracy": accuracy.accuracy_summary,
        "roofline": roofline.roofline_table,
    }


def main(argv=None) -> int:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark keys")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as JSON (CI artifact format)")
    args = ap.parse_args(argv)
    benches = all_benchmarks()
    keys = args.only.split(",") if args.only else list(benches)
    print("name,value,derived")
    failures = 0
    records = []
    for k in keys:
        t0 = time.monotonic()
        try:
            rows = benches[k]()
            wall = time.monotonic() - t0
            for r in rows:
                print(f"{r.name},{r.value:.4f},{r.derived}")
                # every row carries its benchmark's wall time, so a
                # single-row query (one metric across commits) still
                # sees cost drift without joining against _wall_s rows
                records.append({"name": r.name, "value": r.value,
                                "derived": r.derived, "status": r.status,
                                "wall_s": round(wall, 3)})
            print(f"{k}/_wall_s,{wall:.1f},")
            records.append({"name": f"{k}/_wall_s", "value": round(wall, 1),
                            "derived": "", "status": "ok",
                            "wall_s": round(wall, 3)})
        except Exception:
            failures += 1
            wall = time.monotonic() - t0
            err = traceback.format_exc().splitlines()[-1]
            print(f"{k}/_FAILED,-1,{err}")
            records.append({"name": f"{k}/_FAILED", "value": -1,
                            "derived": err, "status": "error",
                            "wall_s": round(wall, 3)})
    if args.json:
        # unified counter snapshot (obs.export): cache hit rates, worker
        # rollups, compile counts — the "how did it run" half of the
        # artifact next to the "what did it score" rows above
        from repro.obs import metrics_snapshot
        metrics = metrics_snapshot(
            extra={"generated_at": time.strftime("%Y-%m-%dT%H:%M:%S%z")})
        with open(args.json, "w") as f:
            json.dump({"benchmarks": records, "metrics": metrics}, f,
                      indent=2)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
