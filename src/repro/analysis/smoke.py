"""--sanitize-smoke: armed interpret-mode kernel runs against oracles.

The static passes prove properties of the source; this lane proves the
kernels' *runtime* contracts once per tier-1 run. It sets REPRO_SANITIZE
in-process (before any kernel of this process has traced), then drives
the EAGER wrapper of every Pallas kernel module on a small scene — eager
calls see concrete outputs, so all the :mod:`repro.kernels.sanitize`
assertions are live, and interpret mode makes OOB block reads fault
instead of wrapping:

  * ``karras_ranges``          — the sanitize path itself runs BOTH the
    pallas kernel and the fused jit twin and asserts they agree,
  * ``ops.bruteforce_knn``     — vs the numpy oracle.

Seconds-scale by construction (N=2000, Q=256, interpret mode); any
contract violation raises, the CLI maps that to exit code 1.
"""
from __future__ import annotations

import os

__all__ = ["run"]


def _expect(ok: bool, what: str):
    if not ok:
        raise AssertionError(f"sanitize smoke: {what}")


def run(n: int = 2000, q: int = 256, seed: int = 0, echo=print) -> int:
    os.environ["REPRO_SANITIZE"] = "1"

    import jax.numpy as jnp
    import numpy as np

    from ..core import morton as M
    from ..kernels import ops, sanitize
    from ..kernels.lbvh_build import karras_ranges

    _expect(sanitize.enabled(), "REPRO_SANITIZE did not arm")

    rng = np.random.default_rng(seed)
    pts_np = rng.uniform(0, 1, (n, 3)).astype(np.float32)
    qp_np = rng.uniform(0, 1, (q, 3)).astype(np.float32)
    pts, qp = jnp.asarray(pts_np), jnp.asarray(qp_np)
    dist = np.sqrt(((qp_np[:, None, :].astype(np.float64)
                     - pts_np[None, :, :]) ** 2).sum(-1))       # (q, n)

    # --- karras ranges: the sanitize path runs pallas AND fused twins ------
    codes = M.morton64(pts)
    codes_s, _ = M.sort_by_morton(codes, jnp.arange(n, dtype=jnp.int32))
    hi, lo, idx = M.combined_delta_key(codes_s, n)
    max_log2 = max((n - 1).bit_length(), 1)
    karras_ranges(hi, lo, idx, n, max_log2)     # twin agreement + contracts
    echo("  karras ok    (pallas twin == fused twin, contracts hold)")

    # --- bruteforce kNN ----------------------------------------------------
    k = 8
    want_d = np.sort(dist, axis=-1)[:, :k]
    d_b, i_b = ops.bruteforce_knn(qp, pts, k)
    _expect(np.allclose(np.asarray(d_b), want_d, rtol=1e-4, atol=1e-5),
            "ops.bruteforce_knn distances differ from the oracle")
    echo(f"  bruteforce ok (k={k})")

    echo("sanitize smoke: all kernel contracts held")
    return 0
