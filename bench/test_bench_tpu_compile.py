"""Each cell's kernel program, compiled at the cell's real shape and batch
for a described TPU v5e (no chip attached): what the TPU compiler would
refuse, or a launch that would not fit one chip, shows here and costs no
chip time. A compile is not a run.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and the tests run
under several workers.
"""
from __future__ import annotations

import json
import os

import pytest

import jax
import jax.numpy as jnp

from bench import harness
from bench.conftest import ROOT

HBM_BYTES = 16 * 10**9
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep these off it
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_kernel_compiles_at_its_shape(one_chip, cell):
    from repro.algos import kernels as K
    from repro.algos.graph_arrays import GraphArrays
    from repro.engine.backends import bucket_dims, source_bucket
    spec = harness.resolve(ROOT, cell)
    v, e = harness.load_module(spec.generator).sizes(spec.config)
    # the upload the backend makes: the graph padded to its shape bucket,
    # with masks where it had to pad
    vb, eb = bucket_dims(v, e)
    masks = (vb, eb) != (v, e)

    def arr(n, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)
    graph = GraphArrays(arr(vb + 1), arr(eb), arr(eb), arr(vb + 1), arr(eb),
                        arr(eb), arr(vb), arr(vb), arr(eb),
                        arr(vb, jnp.bool_) if masks else None,
                        arr(eb, jnp.bool_) if masks else None)
    # a closed loop's burst, or one request of each class of a mix
    for cls in spec.classes:
        batch = source_bucket(spec.burst or cls.sources)
        program = getattr(K, cls.program)
        compiled = jax.jit(program).lower(graph, arr(batch)).compile()
        m = compiled.memory_analysis()
        total = (m.temp_size_in_bytes + m.argument_size_in_bytes
                 + m.output_size_in_bytes)
        assert total <= HBM_BYTES, (
            f"{cls.program}: {total / 1e9:.2f} GB > one v5e chip")
