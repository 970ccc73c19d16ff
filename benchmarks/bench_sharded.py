"""DESIGN.md §11: weak scaling of sharded serving.

Fixed N PER DEVICE, mesh sizes 1/2/4/8 — each size runs in a CPU
subprocess (``JAX_PLATFORMS=cpu``,
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and serves a
fixed request mix through a ``ShardedIndexStore`` + ``QueryServer``.
Inside each subprocess telemetry is enabled, so every mesh size reports
device-fenced per-phase span
summaries (gather / local_traverse / exchange / merge) alongside serve
latency, distributed-refit latency, and build time.

Results merge into ``BENCH_distributed.json`` under ``"weak_scaling"``
(run.py's MERGE_INTO mechanism — the file also carries the §2.3
collective-byte HLO numbers from bench_distributed).

``--smoke`` is the seconds-scale tier-1 invocation: mesh sizes {1, 2},
tiny N, and hard asserts on phase coverage, refit publication, and
conformance of the served results against a brute-force oracle.
"""
import argparse
import json
import os
import subprocess
import sys

from ._util import row

MERGE_INTO = "distributed"     # run.py: merge into BENCH_distributed.json ...
MERGE_KEY = "weak_scaling"     # ... under this key

SMOKE = dict(meshes=(1, 2), n_per_shard=64, n_queries=32, trials=3,
             capacity=8)
FULL = dict(meshes=(1, 2, 4, 8), n_per_shard=2048, n_queries=512, trials=10,
            capacity=32)

_PHASES = ("sharded.gather", "sharded.local_traverse", "sharded.exchange",
           "sharded.merge")

_CODE = """
import json, time
import numpy as np, jax, jax.numpy as jnp
from repro import telemetry
from repro.compat import make_mesh
from repro.core import geometry as G
from repro.service import (QueryServer, ServiceConfig, ShardedIndexStore,
                           knn_request, ray_request, within_request)

R, N_PER, Q, TRIALS, CAP = __R__, __N_PER__, __Q__, __TRIALS__, __CAP__
N = N_PER * R
assert jax.device_count() >= R
telemetry.enable(capacity=65536)

rng = np.random.default_rng(0)
pts = rng.uniform(0, 1, (N, 3)).astype(np.float32)
qa = rng.uniform(0, 1, (Q, 3)).astype(np.float32)
dirs = rng.normal(size=(Q, 3)).astype(np.float32)

cfg = ServiceConfig(capacity=CAP, min_bucket=8, max_bucket=max(Q, 8))
store = ShardedIndexStore(make_mesh((R,), ("data",)), "data")
srv = QueryServer(store=store, config=cfg)

t0 = time.perf_counter()
srv.create_index("default", pts)
build_us = (time.perf_counter() - t0) * 1e6

def mix():
    return [knn_request(qa, 8), within_request(qa, 0.5 / R ** (1 / 3)),
            ray_request(qa, dirs, 4)]

srv.handle(mix())                               # warm every stage plan
telemetry.get_tracer().drain()                  # timed trials only below

serve_us = []
for _ in range(TRIALS):
    t0 = time.perf_counter()
    resp = srv.handle(mix())
    serve_us.append((time.perf_counter() - t0) * 1e6)
phases = telemetry.summarize_spans(telemetry.get_tracer().drain())

t0 = time.perf_counter()
entry = store.update("default", G.Points(jnp.asarray(pts + 0.001)))
refit_us = (time.perf_counter() - t0) * 1e6
assert entry.action == "refit", entry.action

print("RESULT " + json.dumps({
    "shards": R, "n_per_shard": N_PER, "n_total": N, "queries": Q,
    "build_us": round(build_us, 1),
    "serve_us_p50": round(float(np.percentile(serve_us, 50)), 1),
    "serve_us_min": round(min(serve_us), 1),
    "refit_us": round(refit_us, 1),
    "refit_action": entry.action,
    "sah_shards": len(entry.sah),
    "phases": {k: v for k, v in phases.items() if k.startswith("sharded.")},
}))
"""


def _cpu_child_env(repo: str) -> dict:
    """Environment of a CPU rehearsal child: eight forced host devices,
    and JAX_PLATFORMS=cpu so the child never reaches for a chip (a chip
    belongs to one process; the parent or the caller may hold it)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    return env


def _run_mesh(r_shards: int, params: dict) -> dict:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = _cpu_child_env(repo)
    code = (_CODE.replace("__R__", str(r_shards))
            .replace("__N_PER__", str(params["n_per_shard"]))
            .replace("__Q__", str(params["n_queries"]))
            .replace("__TRIALS__", str(params["trials"]))
            .replace("__CAP__", str(params["capacity"])))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=1800)
    if res.returncode != 0:
        raise RuntimeError(f"mesh R={r_shards} failed:\n{res.stdout}\n"
                           f"{res.stderr}")
    for line in res.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"mesh R={r_shards}: no RESULT line:\n{res.stdout}")


def _smoke_conformance():
    """Hard oracle check, single subprocess: sharded serving on 2 shards
    answers a knn mix identically to the brute-force distance matrix."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = _cpu_child_env(repo)
    code = """
import numpy as np, jax.numpy as jnp
from repro.compat import make_mesh
from repro.service import (QueryServer, ServiceConfig, ShardedIndexStore,
                           knn_request)
rng = np.random.default_rng(7)
pts = rng.uniform(0, 1, (128, 3)).astype(np.float32)
qa = rng.uniform(0, 1, (16, 3)).astype(np.float32)
srv = QueryServer(store=ShardedIndexStore(make_mesh((2,), ("data",)),
                                          "data"),
                  config=ServiceConfig(capacity=8, min_bucket=8,
                                       max_bucket=16))
srv.create_index("default", pts)
(resp,) = srv.handle([knn_request(qa, 4)])
D = np.linalg.norm(qa[:, None] - pts[None], axis=-1)
assert np.allclose(resp.dists, np.sort(D, 1)[:, :4], atol=1e-5)
assert resp.stats.route == "sharded"
print("CONFORMANCE OK")
"""
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    if res.returncode != 0 or "CONFORMANCE OK" not in res.stdout:
        raise RuntimeError(f"smoke conformance failed:\n{res.stdout}\n"
                           f"{res.stderr}")


def main(smoke: bool = False) -> dict:
    params = dict(SMOKE if smoke else FULL)
    meshes = {}
    for r_shards in params.pop("meshes"):
        out = _run_mesh(r_shards, params)
        meshes[str(r_shards)] = out
        # every phase must have fired and been fenced on every mesh size —
        # a silent span rename would unhook the report CLI
        missing = [p for p in _PHASES if p not in out["phases"]]
        assert not missing, f"R={r_shards} missing phase spans: {missing}"
        assert out["refit_action"] == "refit"
        assert out["sah_shards"] == r_shards
        phase_us = {p: out["phases"][p]["total_us"] for p in _PHASES}
        worst = max(phase_us, key=phase_us.get)
        row(f"sharded/R{r_shards}/serve_p50", out["serve_us_p50"],
            f"N/dev={out['n_per_shard']},Q={out['queries']},"
            f"worst_phase={worst.removeprefix('sharded.')}")
        row(f"sharded/R{r_shards}/refit", out["refit_us"],
            "per-shard refit + top-bound exchange")
    if smoke:
        _smoke_conformance()
    return {"fixed_n_per_device": params["n_per_shard"], "meshes": meshes}


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale multi-device tier-1 smoke")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    main(smoke=args.smoke)
    if args.smoke:
        print("# bench_sharded smoke OK", file=sys.stderr)
