"""Compile-only checks for the TPU v5e: the kernels and the served read
program, compiled for a described v5e:2x2 topology at SOSD scale (200M
uint64 keys, 1.6 GB) from shapes alone.  Nothing runs; the TPU compiler
refuses here what it would refuse on the chip (block tiling, in-kernel
gathers, memory that does not fit).

The topology is described inside a fixture, never at import or
collection time: only one process at a time may load the TPU library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.core  # noqa: F401 — x64 on, as wherever the service runs
from repro.kernels.bounded_search.kernel import TILE_ROWS, lower_bound_kernel
from repro.kernels.bounded_search.ops import QUERY_CHUNK
from repro.kernels.common import LANES
from repro.kernels.rmi_lookup.kernel import TABLE_BLOCK, rmi_infer_kernel

SOSD_N = 200_000_000          # SOSD's published key count (arXiv 1911.13014)
BUCKET = 4096                 # a served batch bucket
HBM_BYTES = 16 * 2**30        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return NamedSharding(Mesh(np.array(topo.devices[:1]), ("data",)), P())


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mosaic_kernels(text):
    return text.count("tpu_custom_call")


# a served bucket, and the largest launch the wrappers make (its
# per-query scalars fill most of the 1 MiB of scalar memory)
LAUNCHES = [BUCKET, QUERY_CHUNK]


@pytest.mark.parametrize("m", LAUNCHES)
def test_bounded_search_kernel_compiles_at_sosd_scale(one_chip, m):
    rows = -(-SOSD_N // (TILE_ROWS * LANES)) * TILE_ROWS
    q = [_sds((m,), jnp.int32, one_chip)] * 4
    planes = [_sds((rows, LANES), jnp.int32, one_chip)] * 2
    fn = jax.jit(lambda *a: lower_bound_kernel(*a, n=SOSD_N,
                                               interpret=False))
    compiled = fn.lower(*q, *planes).compile()
    assert _mosaic_kernels(compiled.as_text()) >= 1


@pytest.mark.parametrize("m", LAUNCHES)
def test_rmi_stage2_kernel_compiles_at_real_width(one_chip, m):
    table_rows = 2**20
    blocks = [_sds((table_rows // LANES, LANES), jnp.int32, one_chip)] * 3
    fn = jax.jit(lambda b, *t: rmi_infer_kernel(b, *t, interpret=False))
    compiled = fn.lower(_sds((m,), jnp.int32, one_chip),
                        *blocks).compile()
    assert _mosaic_kernels(compiled.as_text()) >= 1
    assert table_rows % TABLE_BLOCK == 0


@pytest.fixture(scope="module")
def pgm_build():
    """The index `chip_smoke.py` serves (PGM, eps=64), built over 1M
    amzn-surrogate keys; the read program is then lowered with the key
    operands at 200M."""
    from repro.core import spec as spec_mod
    from repro.data import sosd

    keys = sosd.generate("amzn", 1_000_000, seed=1)
    return spec_mod.build(spec_mod.IndexSpec("pgm", {"eps": 64}), keys)


def _lower_read_program(build, n, backend, sharding):
    """The served read program (health on: kind "instr") lowered for one
    chip with its operands — keys, index state, kernel planes — given as
    shapes of an ``n``-key generation."""
    import dataclasses

    from repro.core import plan as plan_mod
    from repro.kernels.bounded_search.ops import kernel_planes

    build = dataclasses.replace(build, meta={**build.meta, "n": n})
    data = _sds((n,), jnp.uint64, sharding)
    plan = plan_mod.lower(build, data)
    ops = {"data": data,
           "state": jax.tree.map(lambda x: _sds(x.shape, x.dtype, sharding),
                                 build.state)}
    if backend == "pallas":
        assert plan.kernel_serves("pallas")
        ops["planes"] = tuple(
            _sds(p.shape, p.dtype, sharding)
            for p in jax.eval_shape(kernel_planes, data))
    prog = plan.program("instr", backend=backend, interpret=False)
    return prog.lower(_sds((BUCKET,), jnp.uint64, sharding),
                      _sds((), jnp.int32, sharding), ops)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_served_read_program_compiles_at_sosd_scale(pgm_build, one_chip,
                                                    backend):
    lowered = _lower_read_program(pgm_build, SOSD_N, backend, one_chip)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert mem.argument_size_in_bytes >= SOSD_N * 8     # the keys are operands
    assert total < HBM_BYTES
    if backend == "pallas":
        assert _mosaic_kernels(compiled.as_text()) >= 1
    # no key array is embedded: the program text does not grow with n
    small = _lower_read_program(pgm_build, 1_000_000, backend, one_chip)
    assert len(lowered.as_text()) < len(small.as_text()) + 4096
