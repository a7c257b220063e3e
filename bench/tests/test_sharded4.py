"""`blast-sc1.sharded4` rehearsed on four virtual CPU devices, each run
a process of its own (the device count is fixed when JAX starts):
`correct` is true on the sound program, on the cell's own threshold and
with every bucket sharded, and false for the f32 control and for the
timed path broken underneath."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

CELL = "blast-sc1.sharded4"
CHILD = Path(__file__).with_name("_rehearse.py")


def rehearse(fault, seed, *, shard_all=True, x64=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               REPRO_SIM_X64="1" if x64 else "0")
    args = [sys.executable, str(CHILD), fault,
            *(["--shard-all"] if shard_all else []),
            "--workload", CELL, "--seed", str(seed), "--seconds", "1",
            "--trace", "0", "--rehearse"]
    done = subprocess.run(args, env=env, capture_output=True, text=True,
                          timeout=300)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("shard_all", [False, True],
                         ids=["threshold", "shard-all"])
def test_sound_program_is_correct_on_four_devices(shard_all):
    res = rehearse("none", 2**31 + 21, shard_all=shard_all)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"sweep_rows_per_s", "setup_s"}


def test_f32_control_is_not_correct_on_four_devices():
    res = rehearse("none", 2**31 + 22, x64=False)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["altered", "half", "no-exchange"])
def test_broken_timed_path_is_not_correct_on_four_devices(fault):
    res = rehearse(fault, 2**31 + 23)
    assert not res["correct"], res["checks"]
