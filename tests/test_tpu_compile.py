"""Compile every kernel of the main path for a described TPU v5e chip.

No chip is attached: ``topologies.get_topology_desc`` describes a v5e:2x2
host, and each kernel is lowered with ``interpret=False`` and compiled by
the TPU compiler for one of its chips, at the sizes ``chip_smoke.py``
drives (wifi_dataset with 4000 users, 200,000 wifi rows, 100,000
occupancy rows).  A compile that passes says the chip's compiler accepts
the kernel and its VMEM use; it says nothing about results or time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and xdist workers import every
test file.  The persistent compilation cache is off around these compiles
(a described-device entry cannot be read back without a chip).

The hash-join build and probe kernels do not lower (see
``kernels/ops.py::PALLAS_RUNS``); their tests are strict xfails, so the
change that makes them compile has to flip the marks.
"""

from __future__ import annotations

import functools
import os

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from repro.kernels.bloom_probe import bloom_probe_pallas  # noqa: E402
from repro.kernels.hash_join import (  # noqa: E402
    hash_join_build_pallas,
    hash_join_probe_pallas,
    table_log2cap,
)
from repro.kernels.knn_distance import masked_distance_pallas  # noqa: E402
from repro.kernels.neighbor_agg import (  # noqa: E402
    neighbor_mean_pallas,
    neighbor_mode_pallas,
)
from repro.kernels import ref as kref  # noqa: E402
from repro.kernels.segment_ops import segment_reduce_pallas  # noqa: E402

# the smoke's sizes, after kernels.ops pads them to power-of-two buckets
N_WIFI_KEYS = 1 << 18  # bloom probe of every wifi.mac_addr (200,000)
BLOOM_LOG2M = 20  # BloomFilter default
KNN_BATCH = 1024  # KnnImputer batch
KNN_REF_ROWS = 100_000  # wifi rows that observe lid (~97,000)
KNN_FEATURES = 4  # wifi columns other than the imputed one
KNN_K = 5
MODE_CLASSES = 8192  # distinct neighbour values in a batch: <= 1024 * 5
SEGMENT_ROWS = 1 << 21  # largest grouped join body (~1.3M rows)
SEGMENT_GROUPS = 4096  # users.name / users.mac_addr groups (4,000)
JOIN_BUILD = 100_000  # occupancy side of the lid join
JOIN_PROBE = 4096
JOIN_MAX_DUP = 4096  # occupancy rows per lid (~3,300)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory on one described chip, with JAX's
    persistent compilation cache off for the module's compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args, kernel=True):
    compiled = jax.jit(fn).lower(*args).compile()
    if kernel:
        assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_bloom_probe_compiles(spec):
    fn = functools.partial(bloom_probe_pallas, num_hashes=4,
                           log2m=BLOOM_LOG2M, interpret=False)
    _compile(fn, spec(((1 << BLOOM_LOG2M) // 32,), jnp.uint32),
             spec((N_WIFI_KEYS,), jnp.uint32))


def test_masked_distance_top_k_compiles(spec):
    def knn(q, qm, r, rm):
        d = masked_distance_pallas(q, qm, r, rm, interpret=False)
        return jax.lax.top_k(-d, KNN_K)

    compiled = _compile(knn, spec((KNN_BATCH, KNN_FEATURES)),
                        spec((KNN_BATCH, KNN_FEATURES)),
                        spec((KNN_REF_ROWS, KNN_FEATURES)),
                        spec((KNN_REF_ROWS, KNN_FEATURES)))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 16 * 2**30


def test_neighbor_mode_compiles(spec):
    fn = functools.partial(neighbor_mode_pallas, num_classes=MODE_CLASSES,
                           interpret=False)
    _compile(fn, spec((KNN_BATCH, KNN_K), jnp.int32))


def test_neighbor_mean_compiles(spec):
    fn = functools.partial(neighbor_mean_pallas, interpret=False)
    _compile(fn, spec((KNN_BATCH, KNN_K)))


@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("dtype", ["int32", "float32"])
def test_segment_reduce_compiles(spec, op, dtype):
    fn = functools.partial(segment_reduce_pallas,
                           num_segments=SEGMENT_GROUPS, op=op,
                           interpret=False)
    _compile(fn, spec((SEGMENT_ROWS,), getattr(jnp, dtype)),
             spec((SEGMENT_ROWS,), jnp.int32))


def test_hash_join_ref_probe_compiles(spec):
    """The join path TPU runs: the sorted-probe jnp oracle, no kernel."""
    fn = functools.partial(kref.hash_join_probe_sorted_ref,
                           max_dup=JOIN_MAX_DUP)
    _compile(fn, spec((JOIN_BUILD,), jnp.uint32),
             spec((JOIN_BUILD,), jnp.int32), spec((JOIN_PROBE,), jnp.uint32),
             kernel=False)


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="scalar-indexed table updates have no Mosaic "
                          "lowering (dynamic_slice)")
def test_hash_join_build_compiles(spec):
    fn = functools.partial(hash_join_build_pallas,
                           log2cap=table_log2cap(JOIN_BUILD), interpret=False)
    _compile(fn, spec((JOIN_BUILD,), jnp.uint32))


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="vector gathers from the VMEM table have no Mosaic "
                          "lowering (only 2D gather is supported)")
def test_hash_join_probe_compiles(spec):
    cap = 1 << table_log2cap(JOIN_BUILD)
    fn = functools.partial(hash_join_probe_pallas,
                           log2cap=table_log2cap(JOIN_BUILD),
                           max_dup=JOIN_MAX_DUP, interpret=False)
    _compile(fn, spec((cap,), jnp.uint32), spec((cap,), jnp.int32),
             spec((JOIN_PROBE,), jnp.uint32))
