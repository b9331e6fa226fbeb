"""The check of the ring cell on a small copy, one worker on each of four
host devices: a sound run is correct, and a gossip that leaves every worker
as it was is caught.  Four devices have to be asked for before JAX starts,
so both runs go to one child process."""
import json
import os
import subprocess
import sys

import pytest

import faults
from harness import common

CELL = "olmo1b-ring4-pd-p4"
CHILD = """
import json, sys
import pytest
import faults
out = {}
with pytest.MonkeyPatch.context() as mp:
    out["sound"] = faults.run_tiny(mp, %(cell)r)["values"]
with pytest.MonkeyPatch.context() as mp:
    faults.no_exchange(mp)
    out["no_exchange"] = faults.run_tiny(mp, %(cell)r)["values"]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([
                   common.BENCH, os.path.join(common.BENCH, "tests"),
                   os.path.join(common.ROOT, "src")]))
    res = subprocess.run([sys.executable, "-c", CHILD % {"cell": CELL}],
                         env=env, capture_output=True, text=True,
                         timeout=600, cwd=common.ROOT)
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def _correct(values):
    limits = faults.TINY_CHECK
    return all(values[n] <= lim for n, lim in limits.items())


def test_a_sound_ring_run_is_correct(readings):
    assert _correct(readings["sound"]), readings["sound"]


def test_the_exchange_between_chips_left_out_is_caught(readings):
    assert not _correct(readings["no_exchange"]), readings["no_exchange"]
