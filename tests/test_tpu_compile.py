"""AOT compiles of the main path's kernels for a described v5e:2x2 chip.

Nothing runs: these compiles ask the TPU compiler what it would refuse on
the chip (tiling, VMEM, partitioning) at the repo's real shapes, at no chip
time.  The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every xdist worker imports
this file.  Keep every such compile in this one file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

I32 = jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # graftlint: boundary(no TPU compiler here: skip, never fail)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to a persistent cache but
    # never read back without the chip: keep the cache out of the way
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=I32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("docs,slots,inserts", [
    (8192, 384, 179),  # batch_8k: 256 ops/doc at 70% inserts
    (2048, 384, 134),  # streaming: 192 ops/doc at 70% inserts
])
def test_pallas_insert_compiles(one_chip, docs, slots, inserts):
    from peritext_tpu.ops.pallas_insert import insert_batch_pallas

    state = [_spec((docs, slots), one_chip), _spec((docs, slots), one_chip),
             _spec((docs,), one_chip), _spec((docs,), one_chip, jnp.bool_)]
    streams = [_spec((docs, inserts), one_chip)] * 3
    compiled = insert_batch_pallas.lower(
        *state, *streams, loop_slots=inserts).compile()
    _assert_kernel(compiled)


def _compile_ragged_kernel(one_chip, b, ki, gmax):
    from peritext_tpu.ops.ragged_pallas import ragged_insert_pallas
    from peritext_tpu.store import DEFAULT_PAGE_SIZE

    n = 1 + b * gmax
    args = (
        _spec((n, DEFAULT_PAGE_SIZE), one_chip),
        _spec((n, DEFAULT_PAGE_SIZE), one_chip),
        _spec((b, gmax), one_chip), _spec((b,), one_chip),
        _spec((b,), one_chip), _spec((b,), one_chip),
        _spec((b,), one_chip, jnp.bool_), _spec((b,), one_chip),
        _spec((b, ki), one_chip), _spec((b, ki), one_chip),
        _spec((b, ki), one_chip),
    )
    _assert_kernel(ragged_insert_pallas.lower(*args).compile())


def test_ragged_kernel_compiles_at_8k_docs(one_chip):
    """Per-doc BlockSpecs of (B, 1)/(B, KI) operands were refused by the
    chip's compiler; the page table prefetch overflowed SMEM."""
    _compile_ragged_kernel(one_chip, 8192, 179, 3)


def test_ragged_kernel_compiles_at_book_length(one_chip):
    """batch_longdoc's 4 docs of 182,315 inserts: a whole insert stream per
    doc overflowed SMEM; it is now read a chunk at a time."""
    _compile_ragged_kernel(one_chip, 4, 182320, 1425)


def test_doc_sharded_apply_compiles_on_4_chips(topo):
    """A Mosaic kernel cannot be partitioned automatically: apply_batch_jit
    routes doc-sharded state to the shard_map'd apply."""
    from peritext_tpu.ops.kernel import (
        doc_axis_mesh, doc_sharded_apply, resolve_insert_impl,
    )
    from peritext_tpu.ops.packed import empty_docs
    from peritext_tpu.parallel.mesh import DOC_AXIS
    from peritext_tpu.testing.synth import synth_streams

    mesh = Mesh(np.asarray(topo.devices[:4]), (DOC_AXIS,))
    sharded = NamedSharding(mesh, P(DOC_AXIS))

    def place(tree):
        return jax.tree_util.tree_map(
            lambda x: _spec(x.shape, sharded, x.dtype), tree)

    state = place(jax.eval_shape(
        lambda: empty_docs(8192, 384, 96, tomb_capacity=38)))
    streams = place(jax.eval_shape(
        lambda: synth_streams(8192, 179, 38, 39)))
    impl = resolve_insert_impl(state.elem_id)
    assert impl == "pallas"
    assert doc_axis_mesh(state.elem_id) == (mesh, DOC_AXIS)
    fn = doc_sharded_apply(mesh, DOC_AXIS, insert_impl=impl,
                           insert_loop_slots=179)
    _assert_kernel(fn.lower(state, streams).compile())
