"""Faults planted in the program's timed path, for the tests that see the
check come out false. Each is a context manager that patches the library
while it is open."""
import contextlib
import dataclasses


@contextlib.contextmanager
def _patched(owner, attr, make):
    orig = getattr(owner, attr)
    setattr(owner, attr, make(orig))
    try:
        yield
    finally:
        setattr(owner, attr, orig)


def half_batch():
    """Every dispatched batch computes only its first half of rows; the
    rest are answered with the first row's answers."""
    from repro.service import pipeline

    def make(orig):
        def execute_group(engine, config, entry, group):
            a, b, n = group.a.copy(), group.b, group.n_real
            a[n // 2:n] = a[0]
            if b is not None:
                b = b.copy()
                b[n // 2:n] = b[0]
            return orig(engine, config, entry,
                        dataclasses.replace(group, a=a, b=b))
        return execute_group
    return _patched(pipeline, "execute_group", make)


def altered_answer():
    """The first row of every batch has its first index moved by one."""
    from repro.service import pipeline

    def make(orig):
        def execute_group(engine, config, entry, group):
            out = orig(engine, config, entry, group)
            rid = group.members[0][0]
            idxs = out[rid].idxs.copy()
            idxs[0, 0] += 1
            out[rid] = dataclasses.replace(out[rid], idxs=idxs)
            return out
        return execute_group
    return _patched(pipeline, "execute_group", make)


def stale_version():
    """Updates publish a new version that still holds the old points."""
    from repro.service import index_store

    def make(orig):
        def update(self, name, values):
            return orig(self, name, self.get(name).bvh.values)
        return update
    return _patched(index_store.IndexStore, "update", make)


def no_exchange():
    """The sharded executor's all-to-all leaves every shard's partial
    answers where they were computed."""
    import jax
    return _patched(jax.lax, "all_to_all",
                    lambda orig: lambda x, *a, **k: x)


FAULTS = {"half_batch": half_batch, "altered_answer": altered_answer,
          "stale_version": stale_version, "no_exchange": no_exchange}


def run_tiny(name: str, fault: str | None, seed: int = 5,
             seconds: float | None = None, points: int = 512,
             trace: bool = False):
    """One run of cell `name` at a CPU size, with `fault` planted; returns
    the harness's Outcome. Every answered row is compared."""
    import time

    import jax

    from bench import run
    from bench.tests.conftest import cell_of, tiny

    cell = tiny(cell_of(name), points=points)
    if seconds is None:     # a closed loop answers thousands of rows a second
        seconds = 0.2 if cell.traffic["loop"] == "closed" else 1.0
    old = run.CHECK_ROWS
    run.CHECK_ROWS = 1 << 30
    try:
        with (FAULTS[fault]() if fault else contextlib.nullcontext()):
            return run.run_cell(cell, jax.devices()[:cell.chips], seed,
                                seconds, trace, time.perf_counter())
    finally:
        run.CHECK_ROWS = old

