"""The trace reduction: arithmetic on recorded events, and reading a
profile the CPU records."""
import numpy as np
import pytest

from bench import trace as TR
from bench.trace import Device, Event, Trace


def _trace():
    """Two devices: executables and operations as the TPU names them."""
    def dev(shift):
        mods = [Event("jit_body(1)", 0 + shift, 100),
                Event("jit_local_fn(2)", 150 + shift, 50),
                Event("jit_body(1)", 400, 100)]
        ops = {"%while.3 = (s32[1024]{0}) while(...)": [2, 180],
               "%all-to-all.1 = f32[4,256,10]{2,1,0} all-to-all(...)":
                   [1, 10 + shift],
               "%all-gather-start = f32[1024,3] all-gather-start(...)":
                   [1, 5]}
        return Device(mods, ops)
    host = [("python3", Event("np.asarray(jax.Array)", 210, 180)),
            ("python3", Event("whole thread", 0, 10_000)),
            ("pjrt", Event("PjitFunction(body)", 100, 40))]
    return Trace({"/device:TPU:0": dev(0), "/device:TPU:1": dev(20)}, host,
                 window_s=600e-9)


def test_busy_is_the_union_of_executable_runs():
    tr = _trace()
    assert tr.busy_intervals("/device:TPU:0") == [(0, 100), (150, 200),
                                                  (400, 500)]
    assert tr.busy_intervals("/device:TPU:1") == [(20, 120), (170, 220),
                                                  (400, 500)]
    assert tr.busy_s() == pytest.approx(250e-9)
    assert TR.union([(0, 5), (3, 9), (9, 10), (12, 13)]) == [(0, 10),
                                                              (12, 13)]


def test_modules_ops_and_collectives_by_name():
    tr = _trace()
    assert len(tr.modules("/device:TPU:0", r"^jit_body")) == 2
    assert tr.op_ns("/device:TPU:0", TR.COLLECTIVE) == 15
    assert tr.op_ns("/device:TPU:1", TR.COLLECTIVE) == 35
    assert tr.op_ns("/device:TPU:0", r"^%while") == 180
    top = tr.top_ops(2)
    assert top[0][0].startswith("%while.3") and top[0][1] == pytest.approx(
        180e-9)
    assert top[1][1] == pytest.approx(20e-9)


def test_a_cut_trace_leaves_out_each_device_s_last_run():
    tr = _trace()
    tr.cut = True
    assert [e.name for e in tr.modules("/device:TPU:0", r"^jit_")] == [
        "jit_body(1)", "jit_local_fn(2)"]
    assert tr.busy_s() == pytest.approx(250e-9)     # busy counts every run


def test_idle_gaps_are_named_by_the_host():
    gaps = _trace().idle_gaps(5)
    assert gaps[0] == ["python3: np.asarray(jax.Array)", pytest.approx(
        200e-9)]
    assert gaps[1] == ["pjrt: PjitFunction(body)", pytest.approx(50e-9)]


def test_layer_metrics_read_the_trace():
    from bench import spec
    from bench.run import Run

    class Stats:
        def __init__(self, batches, rows, slots):
            self.batches, self.batch_rows, self.batch_slots = \
                batches, rows, slots

    tr = _trace()
    cell = spec.load_cell("box-16M-4chip.within-bulk")
    run = Run(cell, None, None, Stats(0, 0, 0), Stats(2, 1500, 2048), tr)
    read = {m.name: m.reader for m in cell.per_layer}
    assert read["local_traverse_ms"](run) == pytest.approx(50e-6)
    assert read["collective_ms"](run) == pytest.approx(35e-6 / 2)
    assert read["idle_share.bulk"](run) == pytest.approx(100 * (1 - 250 / 600))
    empty = Run(cell, None, None, Stats(0, 0, 0), Stats(0, 0, 0),
                Trace({}, [], 1.0))
    assert all(r(empty) is None for r in read.values())


def test_load_reads_a_recorded_cpu_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    x = jax.jit(lambda a: (a @ a).sum())(jnp.ones((64, 64)))
    np.asarray(x)
    jax.profiler.stop_trace()
    tr = TR.load(TR.find_xplane(str(tmp_path)), window_s=1.0)
    # the CPU has no device plane: nothing is read as device time
    assert tr.device_names() == [] and tr.busy_s() == 0.0
    assert tr.host and all(isinstance(e, Event) for _, e in tr.host)
