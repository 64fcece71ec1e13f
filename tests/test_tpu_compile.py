"""Ahead-of-time compiles of the serving kernels for a TPU v5e.

Interpret mode never checks tile alignment, scalar or vector memory, so
these tests lower the ops wrappers the executors call (``interpret=False``)
for a described v5e chip at the paper's widths: W = 32 words per row
(1024-document blocks) on a 2^20-row arena. Nothing runs; a compile that
passes here is not a chip run.

The topology is described inside a module fixture: the TPU compiler may be
loaded by one process at a time, and only the worker that is given this
file loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.query import _join_slots, _pad_unique
from repro.kernels import bitslice_score as k
from repro.kernels import ops

R, W = 1 << 20, 32          # 2^20 rows of 1024-document blocks
Q, NB, L = 32, 4, 128       # one full default micro-batch


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _arena(sharding, rows=R, words=W):
    lines = jax.eval_shape(lambda a: ops.pack_lines(a, jnp),
                           jax.ShapeDtypeStruct((rows, words), jnp.uint32))
    return ops.PackedArena(_spec(sharding, lines.shape, jnp.uint32),
                           rows, words)


def _compile(fn, *args, **static):
    return jax.jit(lambda *a: fn(*a, interpret=False, **static)
                   ).lower(*args).compile()


def _tables(sharding, q=Q, nb=NB, ell=L):
    return _spec(sharding, (q, nb, ell)), _spec(sharding, (q, nb, ell))


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def test_lookup_multi_compiles_without_arena_copy(one_chip):
    arena = _arena(one_chip)
    c = _compile(ops.bitslice_lookup_score_multi, arena,
                 *_tables(one_chip))
    assert _kernel_calls(c) == 1
    mem = c.memory_analysis()
    # no relayout or copy of the arena: temporaries stay far below it
    assert mem.temp_size_in_bytes < R * W * 4 // 8


@pytest.mark.parametrize("grid_order", k.GRID_ORDERS)
def test_lookup_multi_grid_orders_compile(one_chip, grid_order):
    _compile(ops.bitslice_lookup_score_multi, _arena(one_chip),
             *_tables(one_chip), grid_order=grid_order)


def test_largest_planner_shape_splits_and_compiles(one_chip):
    """A long-read bucket over many blocks: [64, 16, 512] tables are 2 MiB
    each and would overflow 1 MiB of scalar memory in one call."""
    q, nb, ell = 64, 16, 512
    c = _compile(ops.bitslice_lookup_score_multi, _arena(one_chip),
                 *_tables(one_chip, q, nb, ell))
    assert _kernel_calls(c) == q * nb * ell // ops.SMEM_TABLE_ENTRIES


@pytest.mark.parametrize("n_unique", [ops.DEDUP_VMEM_BYTES // (128 * 4 * 2),
                                      Q * NB * L])
def test_gather_and_dedup_compile(one_chip, n_unique):
    """At the VMEM cap the unique rows stay resident (dedup kernel); the
    largest count ``plan_dedup_batch`` can pad to streams them instead."""
    c = _compile(ops.bitslice_lookup_score_dedup, _arena(one_chip),
                 _spec(one_chip, (_pad_unique(n_unique),)),
                 *_tables(one_chip))
    assert _kernel_calls(c) == 2
    assert c.memory_analysis().temp_size_in_bytes < R * W * 4 // 8


def test_dedup_at_vmem_cap_compiles(one_chip):
    """The largest unique-row tile the dedup kernel keeps in VMEM."""
    u = ops.DEDUP_VMEM_BYTES // (128 * 4 * 2)
    _compile(ops.bitslice_chunk_score_dedup,
             _spec(one_chip, (u, W), jnp.uint32), *_tables(one_chip),
             _spec(one_chip, (Q, NB, W, 32)))


def test_chunk_lookup_compiles(one_chip):
    _compile(ops.bitslice_chunk_score_multi, _arena(one_chip),
             *_tables(one_chip, ell=32), _spec(one_chip, (Q, NB, W, 32)))


def test_compressed_lookup_compiles(one_chip):
    d = 1 << 16
    c = _compile(ops.bitslice_lookup_score_multi_comp,
                 _arena(one_chip, rows=d), _spec(one_chip, (R,)),
                 *_tables(one_chip))
    assert _kernel_calls(c) == 1


def test_vertical_compiles(one_chip):
    _compile(ops.bitslice_score, _spec(one_chip, (L, NB * W), jnp.uint32),
             method="vertical")


@pytest.mark.parametrize("shapes", [[(1024,)] * 8, [(Q, 1024)] * 8,
                                    [(8, 1024)] * 7 + [(8, 512)]],
                         ids=["one-query", "padded-batch", "short-last"])
def test_slot_join_is_one_device_op(one_chip, shapes):
    """A paged batch's per-shard scores are joined by one fused op: every
    device op adds an event, and an idle gap, to each batch's trace."""
    c = _join_slots.lower(*[_spec(one_chip, s) for s in shapes]).compile()
    entry = c.as_text().split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    ops_run = [ln for ln in entry.splitlines()[1:]
               if " parameter(" not in ln]
    assert len(ops_run) == 1 and " fusion(" in ops_run[0], ops_run
