"""Ahead-of-time compiles of the serving and build kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology, and refuses what the chip would
refuse (block shapes off the (8, 128) tiling, shape casts Mosaic cannot
lower, programs that do not fit). Interpret-mode tests cannot see those
faults. Nothing runs here, so these tests say nothing about answers or
times — ``tests/test_kernels.py`` checks answers.

The topology is described only inside the module fixture below: only one
process at a time may load the TPU library, so describing it at import
would break collection under several test workers.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.device_index import _join_packed, _query_batch_sorted_rows
from repro.kernels import ops
from repro.kernels.bitpack import pack_bits

# the chip smoke's serving shapes: V vertices, rows padded to E, and
# the pow2 batch its scheduler flushes
V, E, Q = 16_384, 368, 256
# the device build's shapes: Vp padded vertices, |L| labels, R wave rows
VP, NL, R = 2_048, 8, 64
NUM_MRS = 72          # |L| + |L|^2 minimum repeats at |L|=8, k=2


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args, **static):
    return fn.lower(*args, **static).compile().as_text()


def test_mergejoin_compiles_for_v5e(one_chip):
    rows = [_sds(one_chip, (V, E)) for _ in range(4)]
    qs = [_sds(one_chip, (Q,)) for _ in range(3)]
    text = _compiled_text(ops.mergejoin_query, *rows, *qs, interpret=False)
    assert "tpu_custom_call" in text


def test_mergejoin_row_window_compiles_for_v5e(one_chip):
    # a shard's windowed layout: rows [lo, lo + n) with global query ids
    rows = [_sds(one_chip, (V // 4 + 3, E)) for _ in range(4)]
    qs = [_sds(one_chip, (Q,)) for _ in range(3)]
    text = _compiled_text(ops.mergejoin_query, *rows, *qs, interpret=False,
                          row_base_out=V // 2, row_base_in=V // 2)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("method,row_lo,kernel", [
    ("pallas", 0, True), ("pallas", V // 2, True), ("sorted", V // 2, False)])
def test_packed_join_compiles_for_v5e(one_chip, monkeypatch, method, row_lo,
                                     kernel):
    # the executor's entry: six layout arrays and one packed (3, Q) batch;
    # JAX runs on the CPU here, so the kernel is told it is not
    monkeypatch.setattr(ops, "on_cpu", lambda: False)
    rows = [_sds(one_chip, (V // 4 + 3, E)) for _ in range(6)]
    text = _compiled_text(_join_packed, *rows, _sds(one_chip, (3, Q)),
                          row_lo=row_lo, num_mrs=NUM_MRS, method=method)
    assert ("tpu_custom_call" in text) == kernel


def test_frontier_step_many_compiles_for_v5e(one_chip):
    text = _compiled_text(ops.frontier_wave_packed,
                          _sds(one_chip, (R, VP), jnp.float32),
                          _sds(one_chip, (NL, VP, VP), jnp.float32),
                          _sds(one_chip, (R,)), interpret=False)
    assert "tpu_custom_call" in text


def test_pack_bits_compiles_for_v5e(one_chip):
    text = _compiled_text(jax.jit(pack_bits),
                          _sds(one_chip, (R, VP), jnp.float32))
    assert "tpu_custom_call" not in text     # plain XLA, no kernel


def test_sorted_join_compiles_for_v5e(one_chip):
    keys = [_sds(one_chip, (V, E)) for _ in range(2)]
    q = [_sds(one_chip, (Q,)) for _ in range(5)]
    text = _compiled_text(_query_batch_sorted_rows, *keys, *q, NUM_MRS)
    assert "tpu_custom_call" not in text
