"""Sharded serving demo (DESIGN.md §11) on 8 simulated devices: a
`ShardedIndexStore` builds a DistributedTree per-shard under shard_map and
a `QueryServer` serves mixed traffic against it — then live values drift
and the distributed refit republishes without interrupting serving.

    PYTHONPATH=src python examples/distributed_search.py

(Re-execs itself on the CPU backend with XLA_FLAGS to get 8 host devices.)
"""
import os
import sys

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.execv(sys.executable, [sys.executable] + sys.argv)

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import make_mesh
from repro.core import geometry as G, nearest
from repro.core.distributed import DistributedTree
from repro.data import point_cloud
from repro.service import (QueryServer, ServiceConfig, ShardedIndexStore,
                           knn_request, ray_request, within_request)


def main():
    mesh = make_mesh((8,), ("data",))
    print(f"mesh: {mesh.shape} over {len(jax.devices())} devices")

    # --- build: one local LBVH per shard, published as version 1 ---------
    pts = np.asarray(point_cloud("clusters", 4096, seed=1))
    store = ShardedIndexStore(mesh, "data")
    server = QueryServer(store=store,
                         config=ServiceConfig(capacity=32, min_bucket=8,
                                              max_bucket=512))
    entry = server.create_index("cloud", pts)
    print(f"published v{entry.version}: {entry.tree.n_local} points x "
          f"{entry.tree.R} shards, per-shard SAH "
          f"{min(entry.sah):.1f}..{max(entry.sah):.1f}")

    # --- serve: the same request mix any QueryServer takes ---------------
    rng = np.random.default_rng(2)
    qa = rng.uniform(0, 1, (256, 3)).astype(np.float32)
    tgt = pts[rng.integers(0, len(pts), 64)]
    o = rng.uniform(0, 1, (64, 3)).astype(np.float32)
    knn, within, rays = server.handle([
        knn_request(qa, 4, "cloud"),
        within_request(qa, 0.05, "cloud"),
        ray_request(o, tgt - o, 1, "cloud"),
    ])
    print(f"kNN via route={knn.stats.route!r}: mean 1-NN distance "
          f"{float(knn.dists[:, 0].mean()):.4f} (global indices, "
          f"max={int(knn.idxs.max())})")
    print(f"radius: mean {float(within.counts.mean()):.1f} neighbors; "
          f"overflow={within.overflow}")
    print(f"rays: {float(np.isfinite(rays.dists[:, 0]).mean()):.0%} hit")

    # --- live update: per-shard refit + top-bound exchange ---------------
    drifted = pts + rng.normal(0, 0.002, pts.shape).astype(np.float32)
    entry = server.update_index("cloud", G.Points(jnp.asarray(drifted)))
    print(f"drift -> v{entry.version} via {entry.action!r} "
          f"(worst-shard degradation {entry.degradation:.3f})")
    knn2, = server.handle([knn_request(qa, 4, "cloud")])
    print(f"served on v{knn2.stats.index_version} without a rebuild")

    # scrambling the cloud trips the worst shard's SAH monitor instead
    entry = server.update_index("cloud", G.Points(jnp.asarray(
        rng.permutation(drifted) * 3)))
    print(f"scramble -> v{entry.version} via {entry.action!r}")

    # --- attach-data: the policy-gated value-shipping opt-in -------------
    dt: DistributedTree = store.get("cloud").tree
    res = dt.query(nearest(G.Points(jnp.asarray(qa[:8])), k=2),
                   policy=dt.policy.override(ship_values=True))
    print(f"ship_values=True: QueryResult.values carries matched coords "
          f"{tuple(res.values.coords.shape)} (default ships none, §2.3)")


if __name__ == "__main__":
    main()
