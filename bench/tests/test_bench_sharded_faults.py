"""The four-chip cell's faults, on four CPU devices in a child process
(the device count is fixed when JAX starts)."""
import json
import os
import subprocess
import sys

from bench.tests.conftest import ROOT, SRC

CODE = """
import json
from bench.tests.faults import run_tiny
out = {}
for fault in (None, "half_batch", "altered_answer", "no_exchange"):
    o = run_tiny("box-16M-4chip.within-bulk", fault, points=2048)
    out[str(fault)] = [o.result["correct"], o.check.values,
                       o.result["device"]["count"]]
print(json.dumps(out))
"""


def test_sharded_faults_are_caught():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, SRC]))
    r = subprocess.run([sys.executable, "-c", CODE], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["None"][0] is True and got["None"][2] == 4, got
    for fault in ("half_batch", "altered_answer", "no_exchange"):
        assert got[fault][0] is False, (fault, got[fault])
