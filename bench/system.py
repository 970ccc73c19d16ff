"""The system under test, driven as a user drives it.

One ``ServingPipeline`` over an ``IndexStore`` on one chip, or over a
``ShardedIndexStore(mesh, "data")`` whose 1-D mesh spans the cell's chips.
Requests are made with ``knn_request`` / ``within_request`` and go in
through ``ServingPipeline.submit``; moved points go in through
``update_index``. The benchmark reads the pipeline's and the engine's
counters, and nothing else of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import geometry as G
from repro.service import (PipelineConfig, ServiceConfig, ServingPipeline,
                           ShardedIndexStore, knn_request, within_request)

INDEX = "cloud"


def _shape(a):
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding,
                                weak_type=jax.typeof(a).weak_type)

#: how long a set-up step may wait for the pipeline (a cold compile of the
#: loop traversal at 2^22 points takes about a minute)
SETUP_WAIT_S = 900.0


class System:
    def __init__(self, config: dict, devices: list, traffic):
        self.traffic = traffic
        svc = ServiceConfig(capacity=int(config["within_capacity"]),
                            min_bucket=int(config["min_bucket"]),
                            max_bucket=int(config["max_bucket"]))
        if config["layout"] == "sharded":
            from repro.compat import make_mesh
            self.mesh = make_mesh((len(devices),), ("data",), devices=devices)
            self.rows = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec("data"))
            store = ShardedIndexStore(self.mesh, "data")
        else:
            self.mesh = None
            self.rows = jax.sharding.SingleDeviceSharding(devices[0])
            store = None
        self.pipe = ServingPipeline(store=store,
                                    config=PipelineConfig(service=svc))
        #: (executable, argument shapes) of each query program the warm-up
        #: ran through the engine, for ``memory_report``
        self.programs: list = []

    def close(self):
        self.pipe.close()

    # -- data -----------------------------------------------------------------
    def place(self, pts: np.ndarray) -> G.Points:
        """The cloud on the cell's devices (rows split over the mesh)."""
        return G.Points(jax.device_put(jnp.asarray(pts), self.rows)
                        if self.mesh is None else
                        jax.device_put(pts, self.rows))

    def build(self, values: G.Points) -> int:
        ver = self.pipe.create_index(INDEX, values)
        jax.block_until_ready(ver.tree.trees if self.mesh is not None
                              else ver.bvh.tree)
        return ver.version

    def update(self, values: G.Points, timeout: float) -> tuple[int, str]:
        """Hand moved points to the pipeline and wait until the version
        they make is the one served. Returns (version, action)."""
        errors = self.pipe.stats().maintenance_errors
        self.pipe.update_index(INDEX, values)
        if not self.pipe.wait_maintenance_idle(timeout):
            raise TimeoutError(f"update not published within {timeout} s")
        st = self.pipe.stats()
        if st.maintenance_errors != errors:
            raise RuntimeError("update failed") from st.last_maintenance_error
        ver = self.pipe.store.get(INDEX)
        return ver.version, ver.action

    # -- requests -------------------------------------------------------------
    def request(self, a: np.ndarray):
        t = self.traffic
        if t.kind == "knn":
            return knn_request(a, t.k, INDEX)
        return within_request(a, t.radius, INDEX)

    def submit(self, request):
        return self.pipe.submit(request)

    def warm(self, buckets: list[int], rng: np.random.Generator):
        """Serve one request of each bucket's rows, so that every program
        the window will run is compiled (or read from the cache). The
        engine's query programs are noted with their arguments' shapes as
        they run (the sharded stages do not go through the engine)."""
        engine = self.pipe.engine
        launch = engine._launch
        bucket = [0]

        def noting(fn, args, **kw):
            self.programs.append((f"{kw.get('op', '?')} bucket {bucket[0]}",
                                  fn, jax.tree_util.tree_map(_shape, args)))
            return launch(fn, args, **kw)

        engine._launch = noting
        try:
            for b in buckets:
                bucket[0] = b
                req = self.request(self.traffic.queries(rng, b))
                self.pipe.submit(req).result(SETUP_WAIT_S)
        finally:
            del engine._launch

    def memory_report(self) -> list[str]:
        """One line per query program noted in the warm-up: the sizes XLA's
        ``memory_analysis`` gives for it. The runtime's peak
        (``memory_peak_bytes``) leaves the temporaries out. Each program is
        lowered again from its shapes and its executable read back from
        the compile cache."""
        lines = []
        for op, fn, shapes in self.programs:
            m = fn.lower(*shapes).compile().memory_analysis()
            lines.append(
                f"program {op}: temp_bytes "
                f"{m.temp_size_in_bytes} argument_bytes "
                f"{m.argument_size_in_bytes} output_bytes "
                f"{m.output_size_in_bytes} code_bytes "
                f"{m.generated_code_size_in_bytes}")
        return lines

    # -- counters -------------------------------------------------------------
    def pipeline_stats(self):
        return self.pipe.stats()

    def jit_traces(self) -> int:
        return self.pipe.engine.stats.snapshot().jit_traces
