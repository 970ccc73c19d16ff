"""PLK001/PLK002: BlockSpec-level kernel sanitizer (the --strict passes).

Each kernel module publishes ``REPROLINT_SPECS`` — a zero-arg callable
returning launch specs::

    {"name": "bruteforce-knn@max-k",    # what envelope this pins
     "call": <zero-arg thunk>,          # invokes the kernel wrapper at the
                                        # LARGEST shapes it is declared
                                        # for (jax.eval_shape-safe)
     "budget": 16 * 2**20}              # optional VMEM budget override

The analyzer monkeypatches ``pl.pallas_call`` with a spy and runs every
thunk eagerly — thunks call the RAW (un-jitted) wrapper functions, so no
executable is compiled and re-runs never hit a stale jit cache — then
checks each recorded launch:

* **PLK001** — static VMEM footprint: Σ input-block bytes + output-block
  bytes + scratch bytes must fit the budget (~16 MB of VMEM on TPU v5e).
  Each kernel's declared limits (``PALLAS_MAX_LEAVES``,
  ``REPROLINT_MAX_K``) are the knobs that keep this true, so its specs
  launch at them — tighten a kernel or raise a limit and the gate
  recomputes the consequence.
* **PLK002** — race-free outputs: no two grid cells that can run
  concurrently (i.e. differ along a ``"parallel"`` grid axis) may map to
  the same output block. Cells differing only along ``"arbitrary"``
  (sequential) axes revisit blocks legally — that is the accumulator
  pattern ``bruteforce_knn`` uses.

The spy never executes kernel bodies: it returns abstract zeros shaped
like ``out_shape``, so a spec run costs milliseconds regardless of the
declared worst-case N.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import itertools
import traceback

from .findings import Finding

__all__ = ["run", "capture", "check_launch", "KERNEL_MODULES",
           "DEFAULT_BUDGET"]

#: the kernel modules the sanitizer gates
KERNEL_MODULES = (
    "repro.kernels.lbvh_build",
    "repro.kernels.bruteforce_knn",
)

DEFAULT_BUDGET = 16 * 2 ** 20          # TPU v5e VMEM, bytes

#: full enumeration below this many grid cells; corner sampling above
_ENUM_LIMIT = 512


@dataclasses.dataclass
class Launch:
    """One recorded ``pl.pallas_call`` launch."""
    path: str
    line: int
    grid: tuple
    in_specs: list
    out_specs: list
    out_shape: list
    scratch_shapes: list
    semantics: tuple          # per-grid-axis: "parallel" | "arbitrary"
    arg_shapes: list          # [(shape, dtype)] of the actual operands


def _caller_site(module_file: str):
    for frame in reversed(traceback.extract_stack()):
        if frame.filename.endswith(module_file.rsplit("/", 1)[-1]) \
                and "analysis" not in frame.filename:
            return frame.filename, frame.lineno
    return module_file, 1


@contextlib.contextmanager
def capture(records: list, module_file: str):
    """Patch ``pl.pallas_call`` with a recording spy for the duration."""
    import jax.numpy as jnp
    from jax.experimental import pallas

    real = pallas.pallas_call

    def spy(kernel, *, grid=None, in_specs=None, out_specs=None,
            out_shape=None, scratch_shapes=(), compiler_params=None,
            interpret=False, **kw):
        def call(*args):
            g = (grid,) if isinstance(grid, int) else tuple(grid or ())
            sem = getattr(compiler_params, "dimension_semantics", None)
            sem = tuple(sem) if sem else ("arbitrary",) * len(g)
            outs = out_shape if isinstance(out_shape, (list, tuple)) \
                else [out_shape]
            ospecs = out_specs if isinstance(out_specs, (list, tuple)) \
                else [out_specs]
            path, line = _caller_site(module_file)
            records.append(Launch(
                path=path, line=line, grid=g,
                in_specs=list(in_specs or []), out_specs=list(ospecs),
                out_shape=list(outs), scratch_shapes=list(scratch_shapes),
                semantics=sem,
                arg_shapes=[(tuple(a.shape), a.dtype) for a in args]))
            zeros = [jnp.zeros(s.shape, s.dtype) for s in outs]
            return zeros if isinstance(out_shape, (list, tuple)) else zeros[0]
        return call

    pallas.pallas_call = spy
    try:
        yield
    finally:
        pallas.pallas_call = real


def _bytes_of(shape, dtype) -> int:
    import numpy as np
    total = np.dtype(dtype).itemsize
    for s in shape:
        total *= int(s)
    return total


def _block_bytes(spec, full_shape, dtype) -> int:
    shape = getattr(spec, "block_shape", None) if spec is not None else None
    return _bytes_of(shape if shape is not None else full_shape, dtype)


def _grid_cells(grid: tuple):
    """All cells for small grids; corners + immediate neighbors otherwise
    (index maps in this codebase are affine, so corner cells witness any
    collision a full enumeration would)."""
    if not grid:
        return [()]
    total = 1
    for g in grid:
        total *= g
    if total <= _ENUM_LIMIT:
        axes = [range(g) for g in grid]
    else:
        axes = [sorted({0, 1, g // 2, g - 2, g - 1} & set(range(g)))
                for g in grid]
    return list(itertools.product(*axes))


def check_launch(launch: Launch, budget: int, label: str) -> list:
    findings = []

    # --- PLK001: static VMEM footprint ---------------------------------
    total = 0
    for spec, (shape, dtype) in zip(launch.in_specs, launch.arg_shapes):
        total += _block_bytes(spec, shape, dtype)
    for spec, sds in zip(launch.out_specs, launch.out_shape):
        total += _block_bytes(spec, sds.shape, sds.dtype)
    for sc in launch.scratch_shapes:
        total += _bytes_of(sc.shape, sc.dtype)
    if total > budget:
        findings.append(Finding(
            "PLK001", launch.path, launch.line,
            f"kernel launch [{label}] stages {total / 2**20:.1f} MB of "
            f"blocks into VMEM (budget {budget / 2**20:.1f} MB)",
            hint="lower the kernel's declared limit or tile the "
                 "offending operand instead of staging it whole"))

    # --- PLK002: race-free output index maps ---------------------------
    cells = _grid_cells(launch.grid)
    for oi, spec in enumerate(launch.out_specs):
        index_map = getattr(spec, "index_map", None)
        if index_map is None:
            continue
        owner: dict = {}
        for cell in cells:
            blk = index_map(*cell)
            blk = blk if isinstance(blk, tuple) else (blk,)
            prev = owner.get(blk)
            if prev is None:
                owner[blk] = cell
                continue
            diff_axes = [ax for ax, (a, b) in enumerate(zip(prev, cell))
                         if a != b]
            racy = [ax for ax in diff_axes
                    if launch.semantics[ax] == "parallel"]
            if racy:
                findings.append(Finding(
                    "PLK002", launch.path, launch.line,
                    f"kernel launch [{label}] output #{oi}: grid cells "
                    f"{prev} and {cell} both map output block {blk} but "
                    f"differ along parallel axis {racy[0]}",
                    hint="make the output index_map injective over "
                         "parallel axes, or mark the revisiting axis "
                         "'arbitrary' in dimension_semantics"))
                break
    return findings


def run(modules=KERNEL_MODULES, budget: int = DEFAULT_BUDGET) -> list:
    """Import each kernel module, run its REPROLINT_SPECS thunks under the
    spy, and check every recorded launch. Raises RuntimeError when a
    module lacks specs or a spec records no launch — a silent no-op gate
    is worse than a broken one."""
    findings: list = []
    for name in modules:
        mod = importlib.import_module(name)
        specs_fn = getattr(mod, "REPROLINT_SPECS", None)
        if specs_fn is None:
            raise RuntimeError(f"{name} does not define REPROLINT_SPECS")
        for spec in specs_fn():
            records: list = []
            with capture(records, mod.__file__):
                spec["call"]()
            if not records:
                raise RuntimeError(
                    f"{name} spec {spec['name']!r} recorded no pallas_call "
                    "launch — the spy never fired")
            for launch in records:
                findings += check_launch(
                    launch, spec.get("budget", budget), spec["name"])
    return findings
