"""Each cell's served programs, compiled at the cell's real shape and
batch for a described TPU v5e (no chip attached): what the TPU compiler
would refuse, or a launch that would not fit one chip, shows here and
costs no chip time. A compile is not a run.

The program compiled for a class is the one the backend serves for its
kernel (``build_kernel``), and the class's ``program`` must name it: the
trace readers find the program's device time by that name.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and the tests run
under several workers.
"""
from __future__ import annotations

import collections
import json
import os
import pathlib
import re

import pytest

import jax
import jax.numpy as jnp

from bench import harness
from bench.conftest import (ROOT, TINY, TINY_MIX, TINY_TRAFFIC, add_mix,
                            copy_benchmark)

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


def launch_batches(root: pathlib.Path, spec, v: int, e: int) -> list[int]:
    """Per class, the largest batch the engine can launch for the cell:
    a closed loop's burst, or all of a class's arrivals in a window of
    ``run_seconds`` coalesced into one launch, each padded to its bucket
    and held to the backend's source cap for one chip's memory."""
    from repro.engine.backends import source_bucket, source_cap
    if spec.arrivals is None:
        sources = [spec.burst]
    else:
        seconds = harness.read_json(root / "BENCHMARK.json")["run_seconds"]
        counts = collections.Counter(i for _, i in harness.arrival_schedule(
            spec.arrivals, [c.share for c in spec.classes], seconds))
        sources = [max(counts[i], 1) * c.sources
                   for i, c in enumerate(spec.classes)]
    return [min(source_bucket(n), source_cap(c.kernel, v, e, HBM_BYTES))
            for n, c in zip(sources, spec.classes)]


def check_compiles(root: pathlib.Path, cell: str, one_chip) -> None:
    """Each class's served program compiles for one chip at the cell's
    shape and largest batch, fits its memory, and is found by the
    class's ``program``."""
    from repro.algos.graph_arrays import GraphArrays
    from repro.engine.backends import bucket_dims, build_kernel
    spec = harness.resolve(root, cell)
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
    for cls, batch in zip(spec.classes, launch_batches(root, spec, vb, eb)):
        # jitted as the backend jits it (`_compiled`)
        compiled = jax.jit(build_kernel(cls.kernel)).lower(
            graph, arr(batch)).compile()
        module = re.match(r"HloModule ([^\s,]+)", compiled.as_text()).group(1)
        assert cls.program in module, (
            f"{cls.kernel}: program {cls.program!r} does not name the "
            f"served {module!r}; the trace readers would never find it")
        m = compiled.memory_analysis()
        total = (m.temp_size_in_bytes + m.argument_size_in_bytes
                 + m.output_size_in_bytes)
        assert total <= HBM_BYTES, (
            f"{module} at {batch} sources: {total / 1e9:.2f} GB > one v5e "
            "chip")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_kernel_compiles_at_its_shape(one_chip, cell):
    check_compiles(ROOT, cell, one_chip)


def _tiny_copy(tmp_path: pathlib.Path, mix: dict) -> tuple[pathlib.Path,
                                                          str]:
    root = copy_benchmark(tmp_path, TINY, traffic=TINY_TRAFFIC)
    return root, add_mix(root, mix, name="kron20.mix.open")


def test_a_mix_cell_added_with_files_alone_compiles_what_is_served(
        one_chip, tmp_path):
    root, cell = _tiny_copy(tmp_path, TINY_MIX)
    check_compiles(root, cell, one_chip)


def test_a_program_that_does_not_name_the_served_one_fails(one_chip,
                                                           tmp_path):
    mix = json.loads(json.dumps(TINY_MIX))
    mix["classes"][0]["program"] = "bfs_levels"
    root, cell = _tiny_copy(tmp_path, mix)
    with pytest.raises(AssertionError, match="jit_bfs_multi_steps"):
        check_compiles(root, cell, one_chip)
