"""REPRO_SANITIZE: interpret-mode kernel re-execution with OOB/NaN checks.

``REPRO_SANITIZE=1`` flips every Pallas kernel in this package into
interpret mode (the kernel body runs as pure JAX op-by-op — OOB block
reads fault instead of wrapping) and arms the output assertions below on
every EAGER kernel call. It is a CPU lane: interpret mode never runs on
a chip, so a kernel asked for its interpret default on a TPU backend
with REPRO_SANITIZE set raises. The checks are the kernels' public
contracts:

  * kNN — distances non-NaN and ascending per row, indices in [-1, n);
  * karras ranges — 0 <= first <= i <= last <= n-2+1 and gamma inside
    [first, last) (the split must fall strictly inside the range).

Calls made from inside another trace (the engine's cached executables)
see tracer outputs and skip the concrete checks — the tier-1 sanitize
smoke (``python -m repro.analysis --sanitize-smoke``) drives the eager
paths so every kernel gets at least one armed run.

The env var is read per call for ``enabled()`` but at TRACE time for the
interpret default baked into a jitted wrapper — flip it before the first
kernel call of the process (the smoke lane sets it at entry).
"""
from __future__ import annotations

import os

__all__ = ["enabled", "interpret_default", "is_concrete", "check_knn",
           "check_karras"]


def enabled() -> bool:
    return os.environ.get("REPRO_SANITIZE", "") not in ("", "0", "false")


def interpret_default() -> bool:
    """Interpret-mode default for kernels whose caller passed None:
    non-TPU backends always interpret, TPU never does (REPRO_SANITIZE,
    which needs interpret mode, is refused there)."""
    import jax
    if jax.default_backend() != "tpu":
        return True
    if enabled():
        raise RuntimeError("REPRO_SANITIZE runs kernels in interpret mode, "
                           "which is not allowed on a TPU backend; unset it "
                           "or run with JAX_PLATFORMS=cpu")
    return False


def is_concrete(*arrays) -> bool:
    import jax
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def _fail(kernel: str, what: str):
    raise AssertionError(
        f"REPRO_SANITIZE: {kernel} violated its output contract: {what}")


def check_knn(dists, idxs, *, n: int, kernel: str):
    import jax.numpy as jnp
    if not (enabled() and is_concrete(dists, idxs)):
        return
    if bool(jnp.any(jnp.isnan(dists))):
        _fail(kernel, "NaN distance")
    if dists.shape[1] > 1 and bool(jnp.any(dists[:, 1:] < dists[:, :-1])):
        _fail(kernel, "distances not ascending")
    if idxs.size and (int(jnp.min(idxs)) < -1 or int(jnp.max(idxs)) >= n):
        _fail(kernel, f"neighbor indices outside [-1, {n})")


def check_karras(first, last, gamma, *, n: int, kernel: str):
    import jax.numpy as jnp
    if not (enabled() and is_concrete(first, last, gamma)):
        return
    i = jnp.arange(n - 1, dtype=first.dtype)
    ok = ((first >= 0) & (first <= i) & (i <= last) & (last <= n - 1)
          & (gamma >= first) & (gamma < last))
    if not bool(jnp.all(ok)):
        _fail(kernel, "karras (first, last, gamma) outside the node "
                      "containment invariants")
