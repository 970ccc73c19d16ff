"""RouteTable: declarative, per-hardware engine-crossover policy (DESIGN.md §8).

The QueryEngine dispatches every batched query between three execution
paths (bruteforce / pallas / loop, §3). Where the crossovers sit is a
hardware fact — MXU width, VMEM size, kernel launch cost — not a code
fact, so baking measured constants into :class:`~repro.core.engine.
EngineConfig` (the pre-ISSUE-7 design) welded one machine's measurements
into every deployment. This module replaces them with a declarative
table:

  * a :class:`RouteRule` per op kind (``spatial`` / ``knn`` /
    ``callback``) holding the crossover thresholds and the kernel block
    size for that op;
  * a :class:`RouteTable` bundling the rules with a schema version and a
    :func:`hardware_fingerprint` of the machine that measured them;
  * JSON persistence (``ROUTE_TABLE.json`` at the repo root by default,
    written by ``benchmarks/autotune.py``) with *loud* validation — a
    stale or corrupt table raises, it never silently mis-routes.

Lookup order (most to least specific, DESIGN.md §8):

  1. explicit per-call/per-index policy  (``ExecutionPolicy.route_table``)
  2. engine-level table                  (``EngineConfig.route_table``)
  3. ``REPRO_ENGINE_FORCE``              (pins a route outright, debugging)
  4. persisted autotuned table           (``ROUTE_TABLE.json`` at the
                                          repo root / ``$REPRO_ROUTE_TABLE``)
  5. built-in table for the backend      (:func:`builtin_route_table`:
                                          :meth:`RouteTable.tpu_default` on
                                          TPU, :meth:`RouteTable.default`
                                          elsewhere)

(3) is checked inside the engine's ``_pick`` — a force always wins over
any table, including an explicit one; it exists for A/B debugging only.

A table can only ever change WHICH path serves a query, never the
result: all three paths are exact (§3 invariant, pinned by
``tests/test_build_conformance.py`` with adversarial tables).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import warnings

__all__ = ["RouteRule", "RouteTable", "SCHEMA_VERSION",
           "hardware_fingerprint", "default_route_table",
           "builtin_route_table", "validate_route_table",
           "TPU_TRAVERSAL_REASON"]

SCHEMA_VERSION = 1

#: ops the engine distinguishes when routing (a table may carry any
#: subset; missing ops fall back to the "default" rule).
OPS = ("spatial", "knn", "callback")

_ENV_TABLE = "REPRO_ROUTE_TABLE"        # path override, or "off" to disable
_DEFAULT_BASENAME = "ROUTE_TABLE.json"

#: why the built-in TPU table keeps every op off the fused traversal
#: kernels: the TPU compiler (Mosaic) rejects their per-lane row gathers
#: from the VMEM-staged tree (``jnp.take`` in ``kernels/bvh_traverse.py``
#: and ``kernels/bvh_callback.py``). ``tests/test_tpu_compile.py`` keeps
#: a strict xfail per kernel, so the PR that makes them compile sees it.
TPU_TRAVERSAL_REASON = (
    "the fused traversal kernels (spatial, knn, callback) do not compile "
    "for TPU: Mosaic rejects their in-kernel row gathers with 'ValueError: "
    "Shape mismatch in input, indices and output'")


def hardware_fingerprint() -> dict:
    """Identify the machine/backend a measurement was taken on. Stamped
    into every autotuned table AND every ``BENCH_*.json`` payload so
    recorded latencies are attributable (ISSUE 7 satellite)."""
    import jax
    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "device_count": jax.device_count(),
        "cpu_count": os.cpu_count() or 1,
    }


def _fingerprints_compatible(a: dict, b: dict) -> bool:
    """Same backend + device kind = the measured crossovers transfer.
    jax version / device count drift only warns via the caller."""
    return (a.get("backend") == b.get("backend")
            and a.get("device_kind") == b.get("device_kind"))


@dataclasses.dataclass(frozen=True)
class RouteRule:
    """Crossover thresholds for one op kind.

    bf_max_work:          route to the MXU all-pairs path while N·Q is
                          below this.
    pallas_min_queries /
    pallas_min_leaves:    below these the vmapped while-loop wins
                          (kernel launch + VMEM staging don't amortize).
    pallas_max_nodes:     tree tables larger than this don't fit VMEM;
                          stay on the while-loop path.
    pallas_max_capacity:  fill/kNN/state buffers wider than this per
                          query would blow the kernel's VMEM output
                          block.
    block_q:              queries per kernel grid cell (the autotuned
                          kernel block size).
    """
    bf_max_work: int = 1 << 22
    pallas_min_queries: int = 128
    pallas_min_leaves: int = 256
    pallas_max_nodes: int = 1 << 17
    pallas_max_capacity: int = 4096
    block_q: int = 256

    def replace(self, **kw) -> "RouteRule":
        return dataclasses.replace(self, **kw)


_RULE_FIELDS = tuple(f.name for f in dataclasses.fields(RouteRule))


@dataclasses.dataclass(frozen=True)
class RouteTable:
    """Versioned per-hardware routing policy. Immutable; safe to share
    across engines and threads."""
    rules: dict            # op -> RouteRule ("default" is the fallback)
    fingerprint: dict = dataclasses.field(default_factory=dict)
    build_engine: str = "auto"      # "pallas" | "ref" | "auto" (lbvh.build)
    schema_version: int = SCHEMA_VERSION
    source: str = "defaults"        # "defaults"|"tpu-rule"|"synthesized"|
                                    # "autotuned"|path
    measurements: dict = dataclasses.field(default_factory=dict)
    reason: str = ""                # why a built-in rule departs from the
                                    # defaults (printed by chip_smoke.py)

    # -- lookup ------------------------------------------------------------
    def rule(self, op: str) -> RouteRule:
        return self.rules.get(op) or self.rules.get("default") or RouteRule()

    # -- constructors ------------------------------------------------------
    @classmethod
    def default(cls) -> "RouteTable":
        """Built-in per-op rules. The kNN and callback caps are tighter
        than the spatial fill cap because their VMEM cost differs: a kNN
        candidate list is (block_q, k) float32 + int32 resident for the
        whole sweep, and a callback state row rides in AND out — at the
        spatial cap (4096) either blows the ~16 MB budget once the tree
        tables are staged (the PLK001 sanitizer pins the arithmetic).
        Queries beyond these caps route to the while-loop path."""
        return cls(rules={
            "default": RouteRule(),
            "knn": RouteRule(pallas_max_capacity=256),
            "callback": RouteRule(pallas_max_capacity=1024),
        })

    @classmethod
    def tpu_default(cls) -> "RouteTable":
        """Built-in rules on a TPU backend: the defaults with
        ``pallas_max_nodes=0`` for every op, so no query routes to a
        traversal kernel the TPU compiler refuses
        (:data:`TPU_TRAVERSAL_REASON`). Brute force and the while loop
        keep their default crossovers."""
        base = cls.default()
        return cls(rules={op: r.replace(pallas_max_nodes=0)
                          for op, r in base.rules.items()},
                   source="tpu-rule", reason=TPU_TRAVERSAL_REASON)

    @classmethod
    def single(cls, *, build_engine: str = "auto", source: str = "synthesized",
               **rule_fields) -> "RouteTable":
        """One rule applied to every op — the synthesized-table spelling
        the deprecated EngineConfig crossover fields lower to, and the
        convenient way to pin thresholds in tests."""
        bad = set(rule_fields) - set(_RULE_FIELDS)
        if bad:
            raise TypeError(f"unknown RouteRule fields {sorted(bad)}; "
                            f"valid: {_RULE_FIELDS}")
        return cls(rules={"default": RouteRule(**rule_fields)},
                   build_engine=build_engine, source=source)

    # -- (de)serialization -------------------------------------------------
    def to_dict(self) -> dict:
        return {
            "schema_version": self.schema_version,
            "fingerprint": dict(self.fingerprint),
            "build_engine": self.build_engine,
            "rules": {op: dataclasses.asdict(r)
                      for op, r in sorted(self.rules.items())},
            "measurements": self.measurements,
        }

    @classmethod
    def from_dict(cls, d: dict, *, source: str = "dict") -> "RouteTable":
        problems = validate_route_table(d)
        if problems:
            raise ValueError(
                f"invalid RouteTable ({source}): " + "; ".join(problems))
        rules = {op: RouteRule(**{k: int(v) for k, v in row.items()
                                  if k in _RULE_FIELDS})
                 for op, row in d["rules"].items()}
        return cls(rules=rules, fingerprint=d.get("fingerprint", {}),
                   build_engine=d.get("build_engine", "auto"),
                   schema_version=d["schema_version"], source=source,
                   measurements=d.get("measurements", {}))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2, sort_keys=True)
            f.write("\n")

    @classmethod
    def load(cls, path: str) -> "RouteTable":
        """Load + validate. Raises ValueError on schema problems (a corrupt
        persisted table must fail loudly, not silently-slow)."""
        with open(path) as f:
            d = json.load(f)
        return cls.from_dict(d, source=path)


def validate_route_table(d) -> list[str]:
    """Schema check; returns a list of problems (empty = valid). Used by
    ``benchmarks/autotune.py --validate`` (wired into tier1) and by
    :meth:`RouteTable.from_dict`."""
    problems: list[str] = []
    if not isinstance(d, dict):
        return [f"table must be a JSON object, got {type(d).__name__}"]
    ver = d.get("schema_version")
    if ver != SCHEMA_VERSION:
        problems.append(f"schema_version={ver!r}, expected {SCHEMA_VERSION}")
    rules = d.get("rules")
    if not isinstance(rules, dict) or not rules:
        problems.append("missing/empty 'rules' object")
        return problems
    for op, row in rules.items():
        if op not in OPS and op != "default":
            problems.append(f"unknown op {op!r} (valid: {OPS + ('default',)})")
        if not isinstance(row, dict):
            problems.append(f"rules[{op!r}] must be an object")
            continue
        for k, v in row.items():
            if k not in _RULE_FIELDS:
                problems.append(f"rules[{op!r}] has unknown field {k!r}")
            elif not isinstance(v, int) or isinstance(v, bool) or v < 0:
                problems.append(f"rules[{op!r}].{k} must be a non-negative "
                                f"int, got {v!r}")
        bq = row.get("block_q")
        if isinstance(bq, int) and not isinstance(bq, bool) and bq > 0 \
                and (bq & (bq - 1)):
            problems.append(f"rules[{op!r}].block_q={bq} is not a power of 2")
    be = d.get("build_engine", "auto")
    if be not in ("auto", "pallas", "ref"):
        problems.append(f"build_engine={be!r} not in ('auto', 'pallas', 'ref')")
    return problems


# --- ambient default table -------------------------------------------------

_CACHE: dict = {}


def _default_path() -> str | None:
    env = os.environ.get(_ENV_TABLE)
    if env:
        return None if env.lower() in ("off", "none", "0") else env
    # repo checkout layout: src/repro/core/route_table.py -> repo root.
    # Only the repo's own table counts: a stray ROUTE_TABLE.json in the
    # working directory must not change routing.
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    cand = os.path.join(root, _DEFAULT_BASENAME)
    return cand if os.path.exists(cand) else None


def default_route_table() -> RouteTable | None:
    """The ambient persisted table (lookup step 4), or None when no table
    is persisted / it was measured on different hardware. Cached per
    (path, mtime) so a re-autotune is picked up without a restart."""
    path = _default_path()
    if path is None or not os.path.exists(path):
        return None
    key = (path, os.path.getmtime(path))
    if key in _CACHE:
        return _CACHE[key]
    table = RouteTable.load(path)      # raises loudly on corrupt tables
    fp = hardware_fingerprint()
    if not _fingerprints_compatible(table.fingerprint, fp):
        warnings.warn(
            f"ignoring persisted route table {path}: it was autotuned on "
            f"{table.fingerprint.get('backend')}/"
            f"{table.fingerprint.get('device_kind')} but this process runs "
            f"{fp['backend']}/{fp['device_kind']} — re-run "
            "`python -m benchmarks.autotune` on this machine",
            RuntimeWarning, stacklevel=2)
        table = None
    _CACHE.clear()
    _CACHE[key] = table
    return table


@functools.lru_cache(maxsize=None)
def builtin_route_table() -> RouteTable:
    """Lookup step 5: the built-in table for JAX's default backend.
    Resolved when first asked, never at import, so importing the library
    does not initialize a backend."""
    import jax
    if jax.default_backend() == "tpu":
        return RouteTable.tpu_default()
    return RouteTable.default()


def _reset_cache() -> None:
    """Test hook: forget the cached ambient table."""
    _CACHE.clear()
