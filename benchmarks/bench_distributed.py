"""§2.3: distributed search.

(a) Callback-communication claim: executing the reduction on the
    data-owning shard vs shipping matched values to the originator —
    collective bytes measured from the LOWERED HLO of each path
    (hloanalysis), on an 8-device mesh in a subprocess.
(b) Weak scaling: collective bytes per device as the shard count grows.
"""
import os
import subprocess
import sys

from ._util import row

_CODE = """
import jax, jax.numpy as jnp, numpy as np
from repro.compat import AxisType, make_mesh
from repro.core import geometry as G, predicates as P, callbacks as CB
from repro.core.distributed import DistributedTree, ship_values_baseline
from repro.launch.hloanalysis import analyze

R = __R__
mesh = make_mesh((R,), ("data",), axis_types=(AxisType.Auto,))
N, Q = 1024, 256
rng = np.random.default_rng(0)
pts = jnp.asarray(rng.uniform(0, 1, (N, 3)).astype(np.float32))
qp = jnp.asarray(rng.uniform(0, 1, (Q, 3)).astype(np.float32))
dt = DistributedTree(mesh, "data", pts)

import jax.profiler
# trace the two paths through lowering only (no run needed for bytes)
def lower_bytes(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    return analyze(c.as_text())["collective_bytes"]

def radius_count(q):
    nq = q.shape[0]
    preds = P.intersects(G.Spheres(q, jnp.full((nq,), 0.2, q.dtype)))
    return dt.query(preds, callback=CB.counting())

b_cb = lower_bytes(radius_count, qp)
b_ship = lower_bytes(lambda q: ship_values_baseline(dt, q, 0.2, 64), qp)
print(f"RESULT {R} {b_cb} {b_ship}")
"""


def main():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for r_shards in (2, 4, 8):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"    # CPU rehearsal: never the chip
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={r_shards}"
        env["PYTHONPATH"] = os.path.join(repo, "src")
        out = subprocess.run(
            [sys.executable, "-c", _CODE.replace("__R__", str(r_shards))], env=env,
            capture_output=True, text=True, timeout=900).stdout
        for line in out.splitlines():
            if line.startswith("RESULT"):
                _, rr, b_cb, b_ship = line.split()
                saving = float(b_ship) / max(float(b_cb), 1)
                row(f"distributed/R{rr}/callback_reduce", float(b_cb) / 1e3,
                    "collective KBytes (HLO)")
                row(f"distributed/R{rr}/ship_values", float(b_ship) / 1e3,
                    f"collective KBytes (HLO); callback saves {saving:.1f}x")


if __name__ == "__main__":
    main()
