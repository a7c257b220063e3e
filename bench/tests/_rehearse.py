"""One rehearsal of a cell in this process, with the timed path broken
underneath where asked:

    python bench/tests/_rehearse.py FAULT [--shard-all] <run.py arguments>

FAULT is ``none``, ``altered`` (one makespan of every engine call moved
by 1e-6), ``half`` (half of each batch left out, the rest given their
mean) or ``no-exchange`` (each sharded executable's rows gathered from
its first device alone: every other chip's answers left out).
``--shard-all`` splits every bucket over the mesh, so that a
rehearsal's small DAGs take the sharded path the cell's take on the
chip. Start it with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
for a four-chip cell on the CPU.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def shard_all():
    from repro.core.sweep.engine import SweepEngine
    SweepEngine.bucket_shards = lambda self, n_rows, n_ops: self.n_shards


def altered():
    from repro.core.sweep.engine import SweepEngine
    real = SweepEngine.simulate_batch

    def simulate_batch(self, ops_list, st_list, *, exact=False):
        out = real(self, ops_list, st_list, exact=exact)
        out[len(out) // 2] *= 1 + 1e-6
        return out

    SweepEngine.simulate_batch = simulate_batch


def half():
    from repro.core.sweep.engine import SweepEngine
    real = SweepEngine.simulate_batch

    def simulate_batch(self, ops_list, st_list, *, exact=False):
        keep = max(len(ops_list) // 2, 1)
        out = real(self, ops_list[:keep], st_list[:keep], exact=exact)
        return np.concatenate([out, np.full(len(ops_list) - keep,
                                            out.mean())])

    SweepEngine.simulate_batch = simulate_batch


def no_exchange():
    from repro.core.sweep import shard
    real = shard.sharded_executable

    def sharded_executable(fn, mesh, n_args=2):
        run = real(fn, mesh, n_args)

        def first_device_only(*args):
            out = np.asarray(run(*args))
            return np.tile(out[:len(out) // mesh.size], mesh.size)

        return first_device_only

    shard.sharded_executable = sharded_executable


FAULTS = {"none": lambda: None, "altered": altered, "half": half,
          "no-exchange": no_exchange}


def main(argv):
    fault, *args = argv
    if "--shard-all" in args:
        args.remove("--shard-all")
        shard_all()
    FAULTS[fault]()
    from bench import run
    return run.main(args)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
