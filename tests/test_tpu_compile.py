"""Ahead-of-time compiles of the PSRS path's Pallas kernels for a TPU v5e.

The TPU compiler is installed with JAX, and it compiles for a chip that is
described, not attached: these tests need no TPU.  Each kernel is compiled
at the shapes the executor hands it on the main path (``chip_smoke.py``'s
device tier: v=16, k=4, n=2^25; its mesh phase for the (src_proc,
dst_proc)-tiled staging; the benchmark's NPB IS class A cells, n=2^23, for
the packed context's delivery and merge) and must come out as a
``tpu_custom_call`` — a
kernel refused by Mosaic, or lowered to something else, fails here instead
of on the chip.  Nothing runs, so results are checked elsewhere (the
interpret-mode tests in ``test_kernels.py`` and the chip smoke run).
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.alltoallv_deliver import (assemble_proc_tiles,
                                             deliver_packed, deliver_tiles)
from repro.kernels.bitonic_sort.bitonic_sort import bitonic_sort_rows
from repro.kernels.bitonic_sort.ops import KERNEL_MAX_N
from repro.kernels.kway_merge import kway_merge, merge_tile_grid, unpack_runs

INT_MAX = 2**31 - 1
V, K = 16, 4
N_V = (1 << 25) // V              # keys per context on the device tier
CAP = N_V                         # per-message capacity (PSRS default)
MERGE_TILES = 2 * N_V // 256      # rcap / merge_tile
M = V // 4                        # contexts per device on a 4-chip mesh
MESH_CAP = (1 << 26) // V


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


# The merge's temp bytes at the parent of the block-window compact gather
# (commit 1f9096d), by this file's AOT compile (JAX 0.9.0, v5e:2x2): the
# compiled merge may use at most 1.25 times as much.
PARENT_MERGE_TEMP = {"kway_k4": 413_031_936, "packed_k1": 47_689_728,
                     "packed_k4": 544_994_304}

_GATHER = re.compile(
    r"= \w+\[([\d,]*)\]\S* gather\(.*slice_sizes=\{([\d,]+)\}")


def _assert_merge_gathers_blocks(compiled, k: int, rcap: int,
                                 parent_temp: int) -> None:
    """No gather of single lanes over the k·rcap output slots is left in
    the merge (each window is read as whole blocks), and its transient
    memory stays within 1.25 times the parent's."""
    lane_gathers = []
    for ln in compiled.as_text().splitlines():
        m = _GATHER.search(ln)
        if m and set(m.group(2).split(",")) == {"1"}:
            n = 1
            for d in filter(None, m.group(1).split(",")):
                n *= int(d)
            if n >= k * rcap:
                lane_gathers.append(ln.strip()[:120])
    assert not lane_gathers, lane_gathers
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 1.25 * parent_temp, (temp, parent_temp)


def _assert_kernel(text: str, name: str) -> None:
    calls = [ln.strip() for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert calls, "no tpu_custom_call in the compiled program"
    assert any(re.match(rf"(ROOT )?%\w*{name}\w*(\.\d+)? = ", ln) for ln in calls), (
        name, [ln[:120] for ln in calls])


@pytest.mark.parametrize("masked", [True, False])
def test_deliver_tiles_compiles(one_chip, masked):
    """P == 1 delivery: the [v, v·ω] send word view of the store, counts
    mask and counts transpose as the collective layer passes them."""
    W = jax.ShapeDtypeStruct((V, V * CAP), jnp.uint32, sharding=one_chip)
    C = jax.ShapeDtypeStruct((V, V), jnp.int32, sharding=one_chip)
    Cw = jax.ShapeDtypeStruct((V, V), jnp.uint32, sharding=one_chip)

    def fn(w, c, cw):
        msgs = w.reshape(V, V, CAP)
        if masked:
            out, ct = deliver_tiles(msgs, c, cw, fill=INT_MAX)
        else:
            out, ct = deliver_tiles(msgs, None, cw)
        return out.reshape(V, V * CAP), ct

    _assert_kernel(_compile_text(fn, W, C, Cw), "alltoallv_deliver")


def test_assemble_proc_tiles_compiles(one_chip):
    """P > 1 staging, per device of a 4-chip mesh: the local [m, v·ω] send
    words viewed as [m, P, m, ω], masked, with the counts payload."""
    W = jax.ShapeDtypeStruct((M, V * MESH_CAP), jnp.uint32,
                             sharding=one_chip)
    C = jax.ShapeDtypeStruct((M, 4, M), jnp.int32, sharding=one_chip)
    Cw = jax.ShapeDtypeStruct((M, 4, M), jnp.uint32, sharding=one_chip)

    def fn(w, c, cw):
        return assemble_proc_tiles(w.reshape(M, 4, M, MESH_CAP), c, cw,
                                   fill=INT_MAX)

    _assert_kernel(_compile_text(fn, W, C, Cw), "alltoallv_deliver")


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.uint32])
def test_merge_tile_grid_compiles(one_chip, dtype):
    """The merge stage's tile sort, vmapped over the k resident contexts."""
    T = jax.ShapeDtypeStruct((K, MERGE_TILES, 256), dtype, sharding=one_chip)
    text = _compile_text(jax.vmap(merge_tile_grid), T)
    _assert_kernel(text, "kway_merge")


def test_kway_merge_compiles(one_chip):
    """The whole merge stage at the class-A cell's shape (NPB IS class A,
    v=16, k=4: [k, v, 2^19] receive buckets, rcap 2^20, tile 256),
    vmapped over the k resident contexts: the splitter search's fence
    index and block gathers lower, the compact gather reads blocks and
    not lanes, and the tile merge stays a kernel (``interpret=False``: the
    dispatch the chip's backend takes)."""
    cap = (1 << 23) // V
    B = jax.ShapeDtypeStruct((K, V, cap), jnp.int32, sharding=one_chip)
    C = jax.ShapeDtypeStruct((K, V), jnp.int32, sharding=one_chip)
    merge = jax.vmap(lambda b, c: kway_merge(b, c, rcap=2 * cap, tile=256,
                                             fill=INT_MAX, interpret=False))
    compiled = jax.jit(merge).lower(B, C).compile()
    _assert_kernel(compiled.as_text(), "kway_merge")
    _assert_merge_gathers_blocks(compiled, K, 2 * cap,
                                 PARENT_MERGE_TEMP["kway_k4"])


def test_packed_delivery_compiles(one_chip):
    """The packed Alltoallv of the class-A cells (v=16, 2^19 sorted keys
    per context, a 2^20-word receive field): the staged tiles go through
    the delivery kernel (``interpret=False``: the chip's dispatch)."""
    n_v = (1 << 23) // V
    W = jax.ShapeDtypeStruct((V, n_v), jnp.int32, sharding=one_chip)
    O = jax.ShapeDtypeStruct((V, V + 1), jnp.int32, sharding=one_chip)
    fn = lambda w, o: deliver_packed(w, o, 2 * n_v, fill=INT_MAX,
                                     interpret=False)
    _assert_kernel(_compile_text(fn, W, O), "alltoallv_deliver")


@pytest.mark.parametrize("k", [1, 4])
def test_packed_merge_compiles(one_chip, k):
    """The merge stage of the class-A cells from the packed receive field:
    the runs unpacked in front of the k-way merge, vmapped over the k
    resident contexts (k=4 on the device cell, k=1 on the file cell)."""
    n_v = (1 << 23) // V
    R = jax.ShapeDtypeStruct((k, 2 * n_v), jnp.int32, sharding=one_chip)
    O = jax.ShapeDtypeStruct((k, V + 1), jnp.int32, sharding=one_chip)

    def merge(recv, offs):
        runs, counts = unpack_runs(recv, offs, n_v)
        return kway_merge(runs, counts, rcap=2 * n_v, tile=256,
                          fill=INT_MAX, interpret=False)[0]

    compiled = jax.jit(jax.vmap(merge)).lower(R, O).compile()
    _assert_kernel(compiled.as_text(), "kway_merge")
    _assert_merge_gathers_blocks(compiled, k, 2 * n_v,
                                 PARENT_MERGE_TEMP[f"packed_k{k}"])


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_bitonic_sort_rows_compiles(one_chip, dtype):
    """The local sort at the widest row the size rule sends to the kernel,
    one row per context, vmapped over the k resident contexts."""
    X = jax.ShapeDtypeStruct((K, 1, KERNEL_MAX_N), dtype, sharding=one_chip)
    text = _compile_text(jax.vmap(bitonic_sort_rows), X)
    _assert_kernel(text, "bitonic_sort")
