"""Ahead-of-time compiles of the main path's kernels for a described TPU
v5e (2x2), at real widths. Nothing runs: the TPU compiler (Mosaic for the
Pallas kernels) refuses here what it would refuse on the chip — block
shapes that do not match the tiling, too much fast memory, a program that
cannot be partitioned."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

CAP, DIM = 2048, 128


@pytest.fixture(scope="module")
def no_compile_cache():
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_pairwise_l2_batched_kernel_compiles(one_chip):
    from repro.kernels.pairwise_l2 import pairwise_l2_threshold_batched
    a = _spec((8, CAP, DIM), one_chip)
    compiled = jax.jit(
        lambda u, v: pairwise_l2_threshold_batched(u, v, 0.09)
    ).lower(a, a).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_device_verify_compiles_with_pallas(one_chip, monkeypatch):
    from repro.compute.engine import device_verify
    from repro.kernels import ops
    # steer the kernel wrapper to the compiled (not interpreted) kernel:
    # the process's own backend is the CPU
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    # the join's batch: 32 lanes, and the pooled pair capacity it runs at
    lanes = 32
    count = _spec((lanes,), one_chip, jnp.int32)
    intra = _spec((lanes,), one_chip, jnp.bool_)
    slab = _spec((CAP, DIM), one_chip)
    compiled = device_verify.lower(
        count, count, intra, *([slab] * (2 * lanes)), eps=0.3,
        k_cap=32768, use_pallas=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bucket_assign_kernel_compiles(one_chip):
    from repro.kernels.bucket_assign import bucket_assign
    compiled = jax.jit(bucket_assign).lower(
        _spec((8192, DIM), one_chip), _spec((1024, DIM), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_distributed_verify_compiles_on_four_chip_mesh(topo):
    from repro.core.distributed import verify_edges_compact, with_auto_axes
    mesh = with_auto_axes(jax.make_mesh((4,), ("data",),
                                        devices=topo.devices))
    whole = NamedSharding(mesh, PartitionSpec())
    split = NamedSharding(mesh, PartitionSpec("data"))
    edges = 128
    compiled = verify_edges_compact.lower(
        _spec((48, CAP, DIM), whole), _spec((edges, 2), split, jnp.int32),
        _spec((edges,), split, jnp.int32), _spec((edges,), split, jnp.int32),
        _spec((edges,), split, jnp.bool_), 0.09, 1024, mesh).compile()
    per_device = compiled.memory_analysis().temp_size_in_bytes
    # each chip holds the temporaries of its own quarter of the edges,
    # and compacts them with no running count across chips
    assert per_device < 4 * 2 ** 30, per_device
    text = compiled.as_text()
    assert not any(c in text for c in ("all-reduce", "all-gather",
                                       "reduce-scatter",
                                       "collective-permute")), text[:2000]
    assert np.prod(list(mesh.shape.values())) == 4
