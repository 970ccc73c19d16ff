#!/usr/bin/env python3
"""Bring-up check: the library's main path on a TPU, through the entry
points a user calls, with every sampled answer checked against numpy.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: sharded serving only

One chip: ``ServingPipeline.create_index`` builds a 2^22-point and a
2^16-point index (uniform in the unit cube, 3-D float32, made from
``--seed``), ``warmup`` compiles the bucket ladder, and knn (k=8), within
(radius sized for ~16 neighbours) and ray (k=1) batches of 1024 queries
are served; the small index also serves 64-query batches, which route to
brute force. One ``update_index`` per index then refits it to jittered
points, and one more batch of each kind is served.

Four chips (``--chips 4``): a ``ShardedIndexStore`` over a 4-device mesh
behind a ``QueryServer`` holds 4 x 2^22 points; the same three kinds are
served before and after one distributed refit, and every device's peak
memory must be at least a quarter of the largest (catches code that puts
everything on device 0). No single-device phase runs.

Every batch is checked on 32 sampled queries per kind against a plain
numpy reference (float64 on the host): kNN distances to 1e-5, within
counts and index sets exactly, ray nearest hits under the slab test the
library uses for Points values (part of the rays are aimed at sampled
points so the check is not all misses). A mismatch, a failed request, a
maintenance error or a ticket that raises fails the run.

The script runs on a TPU backend only (it exits non-zero elsewhere,
printing no result) and refuses REPRO_SANITIZE (interpret mode never runs
on the chip). It starts no child processes. Phase times, routes and peak
device memory are printed as information, not as benchmark metrics. The
last line of stdout is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

K_NN = 8                 # knn width
K_RAY = 1                # nearest ray hit
CAPACITY = 64            # within buffer: far above the ~16 expected matches
SAMPLE = 32              # queries per kind checked against numpy
TICKET_TIMEOUT_S = 1800.0


def log(msg: str):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# data and queries (host numpy, from the seed)
# ---------------------------------------------------------------------------

def uniform_points(rng, n: int) -> np.ndarray:
    return rng.random((n, 3), dtype=np.float32)


def radius_for(n: int, neighbours: int = 16) -> float:
    """Ball radius holding `neighbours` points on average at density n."""
    return float((3.0 * neighbours / (4.0 * math.pi * n)) ** (1.0 / 3.0))


def jittered(rng, pts: np.ndarray) -> np.ndarray:
    """Moved copy of `pts`: a tenth of the mean spacing, so the store's
    quality monitor keeps the update a refit."""
    sigma = 0.1 * len(pts) ** (-1.0 / 3.0)
    return (pts + rng.normal(0.0, sigma, pts.shape)).astype(np.float32)


def make_rays(rng, pts: np.ndarray, q: int):
    """Half the rays are aimed along a random axis at sampled points (the
    other two coordinates equal the point's exactly, so the slab test
    hits it); the rest start anywhere in the cube in random directions."""
    half = q // 2
    tgt = pts[rng.integers(0, len(pts), half)]
    axis = rng.integers(0, 3, half)
    sign = rng.choice(np.array([-1.0, 1.0], np.float32), half)
    off = rng.uniform(0.05, 0.5, half).astype(np.float32)
    rows = np.arange(half)
    o_aim = tgt.copy()
    o_aim[rows, axis] = tgt[rows, axis] - sign * off
    d_aim = np.zeros((half, 3), np.float32)
    d_aim[rows, axis] = sign
    o_rnd = rng.random((q - half, 3), dtype=np.float32)
    d_rnd = rng.normal(size=(q - half, 3)).astype(np.float32)
    d_rnd /= np.linalg.norm(d_rnd, axis=1, keepdims=True)
    return (np.concatenate([o_aim, o_rnd]).astype(np.float32),
            np.concatenate([d_aim, d_rnd]).astype(np.float32))


# ---------------------------------------------------------------------------
# numpy reference on sampled rows
# ---------------------------------------------------------------------------

class Reference:
    """Plain numpy answers over one point set. The points are bucketed in
    a uniform grid of GRID^3 cells over the unit cube (points outside it
    fall in the border cells), so a check reads only the cells that can
    hold its answer instead of scanning all N points per query."""

    GRID = 64

    def __init__(self, pts: np.ndarray):
        self.pts = pts
        self.lo, self.hi = pts.min(0), pts.max(0)
        key = self._key(self._cell(pts))
        self.order = np.argsort(key, kind="stable")
        self.starts = np.searchsorted(key[self.order],
                                      np.arange(self.GRID ** 3 + 1))

    def _cell(self, x):
        """Grid cell of each point of `x`, clipped to the grid (monotone:
        a point inside a box lies in the cells between the corners')."""
        g = self.GRID
        return np.clip(np.floor(np.asarray(x, np.float64) * g), 0,
                       g - 1).astype(np.int64)

    def _key(self, cells):
        return (cells[..., 0] * self.GRID + cells[..., 1]) * self.GRID \
            + cells[..., 2]

    def _in_cells(self, cells):
        """Indices of the points in the given (M, 3) cells (clipped to
        the grid)."""
        keys = np.unique(self._key(np.clip(cells, 0, self.GRID - 1)))
        s, e = self.starts[keys], self.starts[keys + 1]
        n = e - s
        first = np.repeat(s - (np.cumsum(n) - n), n)
        return self.order[first + np.arange(n.sum())]

    def _in_box(self, lo, hi):
        """Indices of a superset of the points in the box [lo, hi]."""
        a, b = self._cell(lo), self._cell(hi)
        axes = np.meshgrid(*[np.arange(a[j], b[j] + 1) for j in range(3)],
                           indexing="ij")
        return self._in_cells(np.stack(axes, -1).reshape(-1, 3))

    def _d2_f32(self, c, idx):
        return np.sum((self.pts[idx] - np.asarray(c, np.float32)) ** 2,
                      axis=1)

    def _dist64(self, c, idx):
        return np.linalg.norm(self.pts[idx].astype(np.float64)
                              - np.asarray(c, np.float64), axis=-1)

    def _knn_candidates(self, c, k):
        """Points whose float32 squared distance is within the k-th
        smallest (plus a tie band): grow a box around `c` until the
        band's radius fits inside it, so no point outside can be in it."""
        h = 1.0 / self.GRID
        while True:
            box = self._in_box(c - h, c + h)
            covers_all = len(box) == len(self.pts)
            if len(box) >= k:
                d2 = self._d2_f32(c, box)
                band = np.partition(d2, k - 1)[k - 1] * (1 + 1e-4) + 1e-12
                if covers_all or math.sqrt(band) * (1 + 1e-6) <= h:
                    return box[d2 <= band]
            h *= 2.0

    def check_knn(self, q, dists, idxs, rows):
        k = dists.shape[1]
        for r in rows:
            cand = self._knn_candidates(q[r], k)
            ref = np.sort(self._dist64(q[r], cand))[:k]
            got_i = idxs[r]
            if np.any(got_i < 0) or len(set(got_i.tolist())) != k:
                raise AssertionError(f"knn row {r}: bad indices {got_i}")
            if not np.allclose(dists[r], ref, rtol=0, atol=1e-5):
                raise AssertionError(f"knn row {r}: distances {dists[r]} "
                                     f"!= reference {ref}")
            own = self._dist64(q[r], got_i)
            if not np.allclose(own, ref, rtol=0, atol=1e-5):
                raise AssertionError(f"knn row {r}: returned points lie at "
                                     f"{own}, reference {ref}")

    def check_within(self, c, radius, counts, idxs, rows):
        r64 = float(np.float32(radius))
        reach = r64 * (1 + 1e-3)
        for r in rows:
            box = self._in_box(c[r] - reach, c[r] + reach)
            cand = box[self._d2_f32(c[r], box) <= reach ** 2]
            d = self._dist64(c[r], cand)
            amb = np.abs(d - r64) <= 1e-6          # float32-rounding band
            sure = set(cand[(d <= r64) & ~amb].tolist())
            maybe = sure | set(cand[amb].tolist())
            n = int(counts[r])
            if n > idxs.shape[1]:
                raise AssertionError(f"within row {r}: {n} matches exceed "
                                     f"capacity {idxs.shape[1]}")
            got = idxs[r][:n].tolist()
            if len(set(got)) != n or not (sure <= set(got) <= maybe) \
                    or np.any(idxs[r][n:] != -1):
                raise AssertionError(f"within row {r}: count {n}, indices "
                                     f"{sorted(got)} != reference "
                                     f"{sorted(sure)}")

    def check_ray(self, o, d, ts, idxs, rows):
        """Nearest hit under ``geometry.ray_box`` on the degenerate box
        [p, p] of each point (float64 on candidates near the line)."""
        corners = np.stack(np.meshgrid(*zip(self.lo, self.hi),
                                       indexing="ij"), -1).reshape(-1, 3)
        steps = np.arange(-1, 2)
        ring = np.stack(np.meshgrid(steps, steps, steps, indexing="ij"),
                        -1).reshape(-1, 3)
        hits = 0
        for r in rows:
            oo, dd = o[r], d[r]
            # cells along the ray: samples half a cell apart from the
            # origin to past the farthest point, each with its 26
            # neighbours (a point on the ray is within half a cell of a
            # sample, so in a neighbouring cell of the sample's)
            norm = float(np.linalg.norm(dd))
            reach = float(np.max(np.linalg.norm(corners - oo, axis=1)))
            t = np.arange(0.0, reach / norm + 1.0 / self.GRID,
                          0.5 / (self.GRID * norm))
            cells = self._cell(oo + t[:, None] * dd)
            box = self._in_cells((cells[:, None, :] + ring).reshape(-1, 3))
            # squared distance to the ray's line (float32 prefilter; a hit
            # lies on the line up to rounding)
            p = self.pts[box] - oo
            cx = p[:, 1] * dd[2] - p[:, 2] * dd[1]
            cy = p[:, 2] * dd[0] - p[:, 0] * dd[2]
            cz = p[:, 0] * dd[1] - p[:, 1] * dd[0]
            near = box[cx * cx + cy * cy + cz * cz <= 1e-8 * float(dd @ dd)]
            best_t, best_i = np.inf, -1
            o64, d64 = oo.astype(np.float64), dd.astype(np.float64)
            zero = np.abs(d64) < 1e-30
            inv = 1.0 / np.where(zero, 1.0, d64)
            for i in near:
                p = self.pts[i].astype(np.float64)
                t = (p - o64) * inv
                inside = o64 == p
                tmin = np.where(zero, np.where(inside, -np.inf, np.inf), t)
                tmax = np.where(zero, np.where(inside, np.inf, -np.inf), t)
                lo, hi = tmin.max(), tmax.min()
                if hi >= max(lo, 0.0):
                    t_enter = max(lo, 0.0)
                    if t_enter < best_t:
                        best_t, best_i = t_enter, int(i)
            hits += best_i >= 0
            if int(idxs[r, 0]) != best_i or not (
                    (np.isinf(best_t) and np.isinf(ts[r, 0]))
                    or abs(float(ts[r, 0]) - best_t) <= 1e-5):
                raise AssertionError(
                    f"ray row {r}: got (t={ts[r, 0]}, idx={idxs[r, 0]}), "
                    f"reference (t={best_t}, idx={best_i})")
        return hits


# ---------------------------------------------------------------------------
# one served round: a batch of each kind, checked
# ---------------------------------------------------------------------------

def serve_round(label: str, serve, index: str, ref: Reference, rng, q: int,
                radius: float) -> dict:
    """Serve one knn + within + ray batch of `q` queries each through
    `serve` (requests -> responses, results on the host) and check SAMPLE
    rows of each. Returns {kind: route}."""
    from repro.service import knn_request, ray_request, within_request

    qk = rng.random((q, 3), dtype=np.float32)
    qw = rng.random((q, 3), dtype=np.float32)
    o, d = make_rays(rng, ref.pts, q)
    t0 = time.perf_counter()
    rk, rw, rr = serve([knn_request(qk, K_NN, index),
                        within_request(qw, radius, index),
                        ray_request(o, d, K_RAY, index)])
    t1 = time.perf_counter()
    n_s = min(SAMPLE, q)
    rows = rng.choice(q, n_s, replace=False)
    half = q // 2                                  # rays: aimed half first
    ray_rows = np.concatenate([
        rng.choice(half, n_s // 2, replace=False),
        half + rng.choice(q - half, n_s - n_s // 2, replace=False)])
    ref.check_knn(qk, rk.dists, rk.idxs, rows)
    ref.check_within(qw, radius, rw.counts, rw.idxs, rows)
    hits = ref.check_ray(o, d, rr.dists, rr.idxs, ray_rows)
    if hits == 0:
        raise AssertionError("no sampled ray hit anything: the ray check "
                             "compared misses only")
    log(f"  {label}: serve 3 x {q} queries {t1 - t0:.3f} s, numpy check "
        f"{time.perf_counter() - t1:.3f} s ({n_s} rows per kind, "
        f"{hits} ray hits)")
    for kind, resp in (("knn", rk), ("within", rw), ("ray", rr)):
        st = resp.stats
        if st.service_us:                  # set by the async pipeline only
            log(f"    {kind}: service {st.service_us / 1e3:.3f} ms, "
                f"bucket {st.bucket}, "
                + ("warm" if st.cache_hit else "cold (compiled here)"))
    return {"knn": rk.stats.route, "within": rw.stats.route,
            "ray": rr.stats.route}


def timed(label: str, fn):
    t0 = time.perf_counter()
    out = fn()
    log(f"  {label}: {time.perf_counter() - t0:.3f} s")
    return out


def peak_bytes(dev):
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# one chip: ServingPipeline over a large and a small index
# ---------------------------------------------------------------------------

def smoke_single(*, n_large: int, n_small: int, q: int, small_q: int,
                 seed: int, engine=None) -> dict:
    """Build, warm, serve, refit and serve again on two indexes through
    one ``ServingPipeline``. Returns {phase label: {kind: route}}."""
    import jax
    import jax.numpy as jnp

    from repro.core import geometry as G
    from repro.service import PipelineConfig, ServiceConfig, ServingPipeline

    rng = np.random.default_rng(seed)
    cfg = PipelineConfig(service=ServiceConfig(
        capacity=CAPACITY, min_bucket=min(small_q, q), max_bucket=q))
    routes = {}
    with ServingPipeline(engine=engine, config=cfg) as pipe:
        def serve(reqs):
            tickets = [pipe.submit(r) for r in reqs]
            return [t.result(TICKET_TIMEOUT_S) for t in tickets]

        for name, n, extra_q in (("large", n_large, None),
                                 ("small", n_small, small_q)):
            log(f"[{name}] N={n} points, Q={q} per batch"
                + (f" (+ Q={extra_q} batches)" if extra_q else ""))
            pts = uniform_points(rng, n)
            radius = radius_for(n)

            def build():
                ver = pipe.create_index(name, G.Points(jnp.asarray(pts)))
                jax.block_until_ready(ver.bvh.tree)
                return ver
            timed("build (create_index, incl. compile)", build)
            timed("warmup (bucket ladder compile)", lambda: pipe.warmup(
                name, kinds_ks=[("knn", K_NN), ("ray", K_RAY)]))
            ref = Reference(pts)
            for i in range(2):
                routes[f"{name} Q={q}"] = serve_round(
                    f"round {i + 1}", serve, name, ref, rng, q, radius)
            if extra_q:
                routes[f"{name} Q={extra_q}"] = serve_round(
                    "small batches", serve, name, ref, rng, extra_q, radius)

            moved = jittered(rng, pts)

            def refit():
                pipe.update_index(name, G.Points(jnp.asarray(moved)))
                if not pipe.wait_maintenance_idle(TICKET_TIMEOUT_S):
                    raise AssertionError(f"{name}: update did not finish")
            timed("update_index (background refit)", refit)
            err = pipe.stats().last_maintenance_error
            if err is not None:
                raise err
            ver = pipe.store.get(name)
            if ver.action != "refit" or ver.version != 2:
                raise AssertionError(f"{name}: update published "
                                     f"{ver.action} v{ver.version}, "
                                     "expected refit v2")
            ref = Reference(moved)
            routes[f"{name} Q={q} refit"] = serve_round(
                "after refit", serve, name, ref, rng, q, radius)
            if extra_q:
                routes[f"{name} Q={extra_q} refit"] = serve_round(
                    "small batches after refit", serve, name, ref, rng,
                    extra_q, radius)
            log(f"  peak_bytes_in_use: {peak_bytes(jax.devices()[0])}")

        st = pipe.stats()
        log(f"  pipeline: served={st.served} failed={st.failed} "
            f"refits={st.refits} rebuilds={st.rebuilds} "
            f"maintenance_errors={st.maintenance_errors}")
        if st.failed or st.maintenance_errors or st.refits != 2:
            raise AssertionError(f"pipeline stats: {st}")
    return routes


# ---------------------------------------------------------------------------
# four chips: ShardedIndexStore behind a QueryServer
# ---------------------------------------------------------------------------

def smoke_sharded(mesh, *, n_per_shard: int, q: int, seed: int) -> dict:
    """Build, serve, distributed-refit and serve again on a sharded index
    over every device of `mesh` (axis "data"). Returns the routes and the
    per-device peak bytes."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import geometry as G
    from repro.service import QueryServer, ServiceConfig, ShardedIndexStore

    rng = np.random.default_rng(seed)
    r = int(mesh.shape["data"])
    n = r * n_per_shard
    log(f"[sharded] {r} shards x {n_per_shard} = {n} points, Q={q}")
    rows = NamedSharding(mesh, PartitionSpec("data"))
    srv = QueryServer(store=ShardedIndexStore(mesh, "data"),
                      config=ServiceConfig(capacity=CAPACITY, min_bucket=q,
                                           max_bucket=q))
    pts = uniform_points(rng, n)
    radius = radius_for(n)

    def build():
        ver = srv.create_index("sharded", G.Points(jax.device_put(pts, rows)))
        jax.block_until_ready(ver.tree.trees)
    timed("build (create_index, incl. compile)", build)
    ref = Reference(pts)
    routes = {}
    for i in range(2):
        routes[f"sharded Q={q}"] = serve_round(
            f"round {i + 1}" + (" (incl. compile)" if i == 0 else ""),
            srv.handle, "sharded", ref, rng, q, radius)
    moved = jittered(rng, pts)
    ver = timed("update_index (distributed refit, incl. compile)",
                lambda: srv.update_index(
                    "sharded", G.Points(jax.device_put(moved, rows))))
    if ver.action != "refit":
        raise AssertionError(f"sharded update published {ver.action}, "
                             "expected refit")
    ref = Reference(moved)
    routes[f"sharded Q={q} refit"] = serve_round(
        "after refit", srv.handle, "sharded", ref, rng, q, radius)
    peaks = [peak_bytes(d) for d in mesh.devices.flat]
    log(f"  peak_bytes_in_use per device: {peaks}")
    if None not in peaks and min(peaks) * 4 < max(peaks):
        raise AssertionError(f"device memory is lopsided: {peaks} (a device "
                             "holds under a quarter of the largest)")
    return routes


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_SANITIZE", "") not in ("", "0", "false"):
        print("chip_smoke: REPRO_SANITIZE is set; interpret mode never runs "
              "on the chip", file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU backend, JAX found "
              f"{devs[0].platform}", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devs)}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.compile_cache import configure_compile_cache
    from repro.core import engine as E
    from repro.core import lbvh
    from repro.kernels import lbvh_build

    cache = configure_compile_cache()
    log(f"backend: {jax.default_backend()}  device_kind: "
        f"{devs[0].device_kind}  device_count: {len(devs)}  jax "
        f"{jax.__version__}")
    log(f"compile cache: {cache}")
    log(f"engine rule: brute force while N*Q <= {E.BF_MAX_WORK}, "
        "else the loop")
    log(f"build engine: {lbvh._resolve_build_engine('auto')}; karras "
        f"ranges: jit twin ({lbvh_build.PALLAS_TPU_REASON})")

    if args.chips == 1:
        routes = smoke_single(n_large=1 << 22, n_small=1 << 16, q=1024,
                              small_q=64, seed=args.seed)
        allowed = {E.ROUTE_LOOP, E.ROUTE_BRUTEFORCE}
    else:
        from repro.compat import make_mesh
        mesh = make_mesh((4,), ("data",), devices=devs[:4])
        routes = smoke_sharded(mesh, n_per_shard=1 << 22, q=1024,
                               seed=args.seed)
        allowed = {"sharded"}
    log("routes:")
    for label, by_kind in routes.items():
        log(f"  {label}: " + "  ".join(f"{k}={v}" for k, v in by_kind.items()))
    bad = {(label, k, v) for label, by_kind in routes.items()
           for k, v in by_kind.items() if v not in allowed}
    if bad:
        raise AssertionError(f"routes outside {sorted(allowed)}: "
                             f"{sorted(bad)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
