"""Per-kernel validation: shape/dtype sweeps in interpret mode against the
pure-jnp oracles, plus hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.kernels.alltoallv_deliver.ops import deliver
from repro.kernels.alltoallv_deliver.ref import deliver_ref
from repro.kernels.bitonic_sort.ops import sort as bitonic_sort
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.kway_merge import ops as kway_merge_ops
from repro.kernels.kway_merge import (
    compact_tiles_ref,
    exact_starts_ref,
    kway_merge,
    kway_merge_ref,
    merge_tile_grid,
    sort_tile_rows,
)
from repro.kernels.lru_scan.ops import lru_scan
from repro.kernels.lru_scan.ref import lru_scan_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref

RNG = np.random.default_rng(42)


# --------------------------------------------------------------------------- #
# flash attention                                                              #
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("b,hq,hkv,sq,sk,d", [
    (1, 2, 2, 128, 128, 64),   # MHA
    (2, 4, 2, 64, 64, 32),     # GQA group 2
    (1, 8, 1, 96, 160, 64),    # MQA, uneven seqs
    (1, 2, 1, 33, 70, 16),     # non-block-aligned
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, hq, hkv, sq, sk, d, causal, dtype):
    q = jnp.asarray(RNG.normal(size=(b, hq, sq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, hkv, sk, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, hkv, sk, d)), dtype)
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=64, block_k=64)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


def test_flash_attention_matches_decode_pattern():
    """Sq=1 with a long KV (the serve_step decode shape)."""
    q = jnp.asarray(RNG.normal(size=(2, 4, 1, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 2, 333, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 2, 333, 64)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# --------------------------------------------------------------------------- #
# bitonic sort                                                                 #
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("rows,n", [(1, 2), (4, 64), (2, 1000), (1, 4096)])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_bitonic_sort_sweep(rows, n, dtype):
    if dtype == np.int32:
        x = RNG.integers(-2**31, 2**31 - 1, size=(rows, n)).astype(dtype)
    else:
        x = RNG.normal(size=(rows, n)).astype(dtype)
    out = bitonic_sort(jnp.asarray(x), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.sort(x, axis=-1))


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(-2**31, 2**31 - 1), min_size=1, max_size=300))
def test_bitonic_sort_property(data):
    x = np.asarray(data, np.int32)
    out = bitonic_sort(jnp.asarray(x), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.sort(x))


# --------------------------------------------------------------------------- #
# alltoallv direct delivery                                                    #
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("v,omega", [(2, 8), (6, 32), (8, 128), (4, 129)])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_deliver_sweep(v, omega, dtype):
    msgs = jnp.asarray(RNG.normal(size=(v, v, omega)) * 100, dtype)
    cnts = jnp.asarray(RNG.integers(0, omega + 1, (v, v)), jnp.int32)
    out = deliver(msgs, cnts, interpret=True)
    ref = deliver_ref(msgs, cnts)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("v,omega", [(4, 129), (3, 200), (2, 257), (5, 64)])
@pytest.mark.parametrize("counts_kind", ["random", "zero", "full"])
def test_deliver_tiled_grid_equivalence(v, omega, counts_kind):
    """ω-tiled (v, v, ω/ωt) grid vs the oracle, covering ω that is not a
    multiple of the 128-lane tile, all-zero counts, and full counts."""
    msgs = jnp.asarray(RNG.normal(size=(v, v, omega)) * 100, jnp.int32)
    if counts_kind == "random":
        cnts = jnp.asarray(RNG.integers(0, omega + 1, (v, v)), jnp.int32)
    elif counts_kind == "zero":
        cnts = jnp.zeros((v, v), jnp.int32)
    else:
        cnts = jnp.full((v, v), omega, jnp.int32)
    out = deliver(msgs, cnts, fill=-3, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(deliver_ref(msgs, cnts, fill=-3))
    )


def test_deliver_fused_counts_transpose():
    """The counts transpose rides in the same pallas_call as a second
    output: ct[d, s] == counts_payload[s, d], bit-exact for raw words."""
    from repro.kernels.alltoallv_deliver import deliver_fused

    v, omega = 6, 130
    msgs = jnp.asarray(RNG.integers(-1000, 1000, (v, v, omega)), jnp.int32)
    cnts = jnp.asarray(RNG.integers(0, omega + 1, (v, v)), jnp.int32)
    cw = jnp.asarray(RNG.integers(0, 2**32, (v, v), dtype=np.uint32))

    out, ct = deliver_fused(msgs, cnts, cw, fill=-1, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(deliver_ref(msgs, cnts, fill=-1))
    )
    np.testing.assert_array_equal(np.asarray(ct), np.asarray(cw).T)

    # No fill → verbatim tile copy (pure permuted-BlockSpec delivery).
    out2, ct2 = deliver_fused(msgs, None, cw, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out2), np.swapaxes(np.asarray(msgs), 0, 1)
    )
    np.testing.assert_array_equal(np.asarray(ct2), np.asarray(cw).T)


def test_deliver_auto_backend_matches_interpret():
    """interpret=None auto-selects a backend; the result must equal the
    interpret-mode kernel bit-for-bit."""
    v, omega = 4, 133
    msgs = jnp.asarray(RNG.integers(-1000, 1000, (v, v, omega)), jnp.int32)
    cnts = jnp.asarray(RNG.integers(0, omega + 1, (v, v)), jnp.int32)
    auto = deliver(msgs, cnts, fill=7)
    interp = deliver(msgs, cnts, fill=7, interpret=True)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(interp))


def test_psrs_bit_identical_across_use_kernel():
    """End-to-end: psrs_sort through the fused kernel path and through the
    seed dense path must agree bit-for-bit (and with the oracle)."""
    from repro.pems_apps import psrs_sort
    x = RNG.integers(-2**30, 2**30, size=1024, dtype=np.int32)
    on = psrs_sort(x, v=8, k=2, use_kernel=True)
    off = psrs_sort(x, v=8, k=2, use_kernel=False)
    np.testing.assert_array_equal(on, off)
    np.testing.assert_array_equal(on, np.sort(x))


@pytest.mark.parametrize("s,Pn,d,omega", [
    (2, 2, 4, 8), (1, 4, 2, 129), (2, 3, 3, 200), (4, 2, 4, 64),
])
@pytest.mark.parametrize("counts_kind", ["random", "zero", "full"])
def test_assemble_proc_tiled_grid_equivalence(s, Pn, d, omega, counts_kind):
    """The (src_proc, dst_proc)-tiled mesh grid vs its oracle: the α-chunk
    [s, P, d, ω] is staged as out[p, dl, j] = msgs[j, p, dl], source-side
    boundary mask and counts transpose fused — covering ragged ω-tiles and
    degenerate counts."""
    from repro.kernels.alltoallv_deliver import assemble_proc_tiles
    from repro.kernels.alltoallv_deliver.ref import assemble_proc_ref

    msgs = jnp.asarray(RNG.integers(-1000, 1000, (s, Pn, d, omega)), jnp.int32)
    if counts_kind == "random":
        cnts = jnp.asarray(RNG.integers(0, omega + 1, (s, Pn, d)), jnp.int32)
    elif counts_kind == "zero":
        cnts = jnp.zeros((s, Pn, d), jnp.int32)
    else:
        cnts = jnp.full((s, Pn, d), omega, jnp.int32)
    cw = jnp.asarray(RNG.integers(0, 2**32, (s, Pn, d), dtype=np.uint32))

    out, ct = assemble_proc_tiles(msgs, cnts, cw, fill=-3, interpret=True)
    ro, rc = assemble_proc_ref(msgs, cnts, cw, fill=-3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ro))
    np.testing.assert_array_equal(np.asarray(ct), np.asarray(rc))

    # No fill → verbatim permuted staging; no payload → single output.
    out2, ct2 = assemble_proc_tiles(msgs, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out2), np.moveaxis(np.asarray(msgs), 0, 2)
    )
    assert ct2 is None


def test_assemble_proc_fused_auto_backend_matches_interpret():
    from repro.kernels.alltoallv_deliver import (
        assemble_proc_fused,
        assemble_proc_tiles,
    )

    s, Pn, d, omega = 2, 2, 3, 133
    msgs = jnp.asarray(RNG.integers(-1000, 1000, (s, Pn, d, omega)), jnp.int32)
    cnts = jnp.asarray(RNG.integers(0, omega + 1, (s, Pn, d)), jnp.int32)
    auto, _ = assemble_proc_fused(msgs, cnts, fill=7)
    interp, _ = assemble_proc_tiles(msgs, cnts, fill=7, interpret=True)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(interp))


@pytest.mark.parametrize("dtype,bad_fill", [
    (jnp.int8, np.iinfo(np.int32).max),      # would wrap to -1
    (jnp.uint16, np.iinfo(np.int32).max),    # would wrap to 65535
    (jnp.uint16, -1),                        # negative on unsigned
    (jnp.int32, 2**31),                      # one past the max
    (jnp.uint32, -1),
])
def test_deliver_fill_out_of_range_rejected(dtype, bad_fill):
    """fill is cast to the payload dtype inside the kernel trace; an
    unrepresentable value used to wrap silently (fill=INT_MAX on int8
    arrives as -1).  Every delivery entry point now rejects it."""
    from repro.kernels.alltoallv_deliver import (
        assemble_proc_fused,
        check_fill_range,
        deliver_fused,
    )

    v, omega = 2, 8
    msgs = jnp.zeros((v, v, omega), dtype)
    cnts = jnp.ones((v, v), jnp.int32)
    with pytest.raises(ValueError, match="fill"):
        check_fill_range(bad_fill, dtype)
    with pytest.raises(ValueError, match="fill"):
        deliver(msgs, cnts, fill=bad_fill, interpret=True)
    with pytest.raises(ValueError, match="fill"):
        deliver_fused(msgs, cnts, fill=bad_fill, interpret=True)
    with pytest.raises(ValueError, match="fill"):
        assemble_proc_fused(msgs[:, None], cnts[:, None], fill=bad_fill,
                            interpret=True)


def test_deliver_fill_in_range_accepted():
    from repro.kernels.alltoallv_deliver import check_fill_range

    check_fill_range(np.iinfo(np.int32).max, jnp.int32)   # the PSRS sentinel
    check_fill_range(-128, jnp.int8)
    check_fill_range(65535, jnp.uint16)
    check_fill_range(2**32 - 1, jnp.uint32)
    check_fill_range(-1.5, jnp.float32)
    with pytest.raises(ValueError, match="fill"):
        check_fill_range(1e39, jnp.float32)               # overflows to inf
    with pytest.raises(ValueError, match="fill"):
        check_fill_range(2.5, jnp.int32)                  # non-integral


def test_alltoallv_fill_out_of_range_rejected():
    """The collective layer checks fill against the send field's dtype
    before any trace work on every implementation path."""
    from repro.core import ContextLayout, Pems, PemsConfig

    v = 4
    lo = (ContextLayout()
          .add("send", (v, 2), jnp.uint32).add("recv", (v, 2), jnp.uint32)
          .add("scnt", (v,), jnp.int32).add("rcnt", (v,), jnp.int32))
    for use_kernel in (True, False):
        pems = Pems(PemsConfig(v=v), lo)
        with pytest.raises(ValueError, match="fill"):
            pems.alltoallv(pems.init(), "send", "recv", "scnt", "rcnt",
                           fill=-1, use_kernel=use_kernel)


def test_deliver_boundary_masking():
    """The boundary fix-up: bytes past counts[s, d] never leak through."""
    v, omega = 4, 16
    msgs = jnp.full((v, v, omega), 7, jnp.int32)
    cnts = jnp.zeros((v, v), jnp.int32).at[1, 2].set(5)
    out = np.asarray(deliver(msgs, cnts, fill=-1, interpret=True))
    assert (out[2, 1, :5] == 7).all() and (out[2, 1, 5:] == -1).all()
    mask = np.ones((v, v), bool)
    mask[2, 1] = False
    assert (out[mask] == -1).all()


# --------------------------------------------------------------------------- #
# lru scan                                                                     #
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("b,s,d,chunk", [
    (1, 32, 8, 8), (2, 128, 16, 32), (1, 77, 4, 16), (3, 256, 2, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lru_scan_sweep(b, s, d, chunk, dtype):
    a = jnp.asarray(RNG.uniform(0.2, 0.999, (b, s, d)), dtype)
    x = jnp.asarray(RNG.normal(size=(b, s, d)), dtype)
    out = lru_scan(a, x, chunk=chunk, interpret=True)
    ref = lru_scan_ref(a, x)
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([16, 48, 128]))
def test_lru_scan_property(seed, s):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.uniform(0.0, 1.0, (1, s, 4)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, s, 4)), jnp.float32)
    out = lru_scan(a, x, chunk=16, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(lru_scan_ref(a, x)), atol=1e-4
    )


# --------------------------------------------------------------------------- #
# ssd scan                                                                     #
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("b,h,s,p,n,chunk", [
    (1, 1, 32, 8, 4, 8),
    (2, 3, 64, 16, 8, 16),
    (1, 2, 100, 8, 16, 32),    # padded sequence
])
def test_ssd_scan_sweep(b, h, s, p, n, chunk):
    x = jnp.asarray(RNG.normal(size=(b, h, s, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.3, (b, h, s)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.3, 2.0, (h,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    out = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    ref = ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_ssd_scan_chunk_invariance():
    """Chunk size is an implementation detail: results must match across
    chunkings (the EM block-size independence property)."""
    b, h, s, p, n = 1, 2, 64, 8, 8
    x = jnp.asarray(RNG.normal(size=(b, h, s, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.3, (b, h, s)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.3, 2.0, (h,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    outs = [
        np.asarray(ssd_scan(x, dt, A, Bm, Cm, chunk=c, interpret=True))
        for c in (8, 16, 64)
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, atol=2e-4)


# --------------------------------------------------------------------------- #
# k-way merge                                                                  #
# --------------------------------------------------------------------------- #

def _merge_case(v, cap, dtype, kind, rng=None):
    """Sorted buckets [v, cap] (garbage past counts, as after delivery) and
    per-bucket counts for the given input shape family."""
    rng = RNG if rng is None else rng
    info = np.iinfo(dtype)
    if kind == "random":
        raw = rng.integers(info.min, info.max, size=(v, cap),
                           dtype=dtype, endpoint=True)
    elif kind == "dups":          # duplicate-heavy: splitter tie-breaking
        raw = (rng.integers(-3, 4, size=(v, cap)) % np.uint64(2**32)
               ).astype(dtype) if dtype == np.uint32 else \
              rng.integers(-3, 4, size=(v, cap)).astype(dtype)
    elif kind == "fillmax":       # every lane at the fill sentinel
        raw = np.full((v, cap), info.max, dtype)
    else:                         # presorted: already globally ascending
        raw = np.sort(rng.integers(info.min, info.max, size=(v, cap),
                                   dtype=dtype, endpoint=True), axis=None
                      ).reshape(v, cap)
    counts = rng.integers(0, cap + 1, size=v).astype(np.int32)
    lane = np.arange(cap)
    buckets = raw.copy()
    for j in range(v):            # sort the valid prefix, garbage the rest
        buckets[j, :counts[j]] = np.sort(raw[j, :counts[j]])
        buckets[j, counts[j]:] = raw[j, ::-1][lane[counts[j]:] % cap]
    return buckets, counts


@pytest.mark.parametrize("v,cap,rcap", [
    (1, 64, 128), (2, 100, 200), (5, 17, 34), (8, 64, 128), (6, 50, 90),
])
@pytest.mark.parametrize("tile", [8, 64, 256])
@pytest.mark.parametrize("dtype", [np.int32, np.uint32])
@pytest.mark.parametrize("kind", ["random", "dups", "fillmax", "presorted"])
def test_kway_merge_sweep(v, cap, rcap, tile, dtype, kind):
    """Fallback path vs the oracle across shapes × tile widths × dtypes,
    including all-sentinel lanes, duplicate-heavy and presorted inputs."""
    buckets, counts = _merge_case(v, cap, dtype, kind)
    fill = int(np.iinfo(dtype).max)
    merged, total, over = kway_merge(
        jnp.asarray(buckets), jnp.asarray(counts), rcap=rcap, tile=tile,
        fill=fill, use_kernel=False)
    ref = kway_merge_ref(jnp.asarray(buckets), jnp.asarray(counts),
                         rcap=rcap, fill=fill)
    np.testing.assert_array_equal(np.asarray(merged), np.asarray(ref))
    assert int(total) == int(counts.sum())
    assert bool(over) == (int(counts.sum()) > rcap)


@pytest.mark.parametrize("v,cap,rcap,tile", [
    (2, 100, 200, 64), (8, 64, 128, 16), (3, 33, 50, 8), (6, 50, 90, 64),
])
def test_kway_merge_tile_grid_equivalence(v, cap, rcap, tile):
    """Interpret-mode Pallas grid vs the oracle and vs the batched jnp
    network: all three bit-identical."""
    buckets, counts = _merge_case(v, cap, np.int32, "random")
    fill = np.iinfo(np.int32).max
    grid, *_ = kway_merge(jnp.asarray(buckets), jnp.asarray(counts),
                          rcap=rcap, tile=tile, fill=fill, interpret=True)
    fall, *_ = kway_merge(jnp.asarray(buckets), jnp.asarray(counts),
                          rcap=rcap, tile=tile, fill=fill, use_kernel=False)
    ref = kway_merge_ref(jnp.asarray(buckets), jnp.asarray(counts),
                         rcap=rcap, fill=fill)
    np.testing.assert_array_equal(np.asarray(grid), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(fall), np.asarray(ref))


def test_kway_merge_auto_backend_matches_interpret():
    """interpret=None auto-selects a backend; must equal the interpret-mode
    grid bit-for-bit (the deliver kernel's dispatch contract)."""
    buckets, counts = _merge_case(4, 80, np.int32, "dups")
    fill = np.iinfo(np.int32).max
    auto, *_ = kway_merge(jnp.asarray(buckets), jnp.asarray(counts),
                          rcap=160, tile=32, fill=fill)
    interp, *_ = kway_merge(jnp.asarray(buckets), jnp.asarray(counts),
                            rcap=160, tile=32, fill=fill, interpret=True)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(interp))


def test_kway_merge_sort_tile_rows_oracle():
    """The per-tile sort primitive alone: the batched bitonic network equals
    jnp-less numpy row sort, across widths and batch shapes."""
    for shape in ((3, 8), (3, 64), (5, 2, 16), (1, 32)):
        x = RNG.integers(-1000, 1000, size=shape).astype(np.int32)
        out = sort_tile_rows(jnp.asarray(x))
        np.testing.assert_array_equal(np.asarray(out), np.sort(x, axis=-1))
    u = RNG.integers(0, 2**32, size=(4, 128), dtype=np.uint64)
    u = u.astype(np.uint32)
    np.testing.assert_array_equal(
        np.asarray(sort_tile_rows(jnp.asarray(u))), np.sort(u, axis=-1))


def test_kway_merge_grid_matches_batched_network():
    """merge_tile_grid (interpret) over a [G, tile] batch equals the batched
    jnp network — the kernel body and the fallback are the same sort."""
    x = RNG.integers(-10**6, 10**6, size=(5, 64)).astype(np.int32)
    g = merge_tile_grid(jnp.asarray(x), interpret=True)
    t = sort_tile_rows(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(g), np.asarray(t))


def test_kway_merge_validation():
    buckets = jnp.zeros((2, 8), jnp.int32)
    counts = jnp.ones((2,), jnp.int32)
    imax = np.iinfo(np.int32).max
    with pytest.raises(ValueError, match="tile"):
        kway_merge(buckets, counts, rcap=4, tile=12, fill=imax)
    with pytest.raises(ValueError, match="rcap"):
        kway_merge(buckets, counts, rcap=0, fill=imax)
    with pytest.raises(ValueError, match="fill"):
        kway_merge(buckets, counts, rcap=4, fill=0)
    with pytest.raises(ValueError, match="dtypes"):
        kway_merge(jnp.zeros((2, 8), jnp.float32), counts, rcap=4,
                   fill=np.finfo(np.float32).max)
    with pytest.raises(ValueError, match="buckets"):
        kway_merge(jnp.zeros((8,), jnp.int32), counts, rcap=4, fill=imax)


def test_kway_merge_overflow_boundary():
    """total == rcap ± 1 at the op level: the flag trips exactly when the
    received population exceeds rcap, and the merged prefix is still the
    correct lowest-rcap either way."""
    v, cap = 4, 32
    buckets, counts = _merge_case(v, cap, np.int32, "random")
    total = int(counts.sum())
    assert total >= 2
    fill = np.iinfo(np.int32).max
    for rcap, expect in ((total - 1, 1), (total, 0), (total + 1, 0)):
        merged, tot, over = kway_merge(
            jnp.asarray(buckets), jnp.asarray(counts), rcap=rcap, tile=16,
            fill=fill, use_kernel=False)
        assert int(tot) == total and int(over) == expect
        ref = kway_merge_ref(jnp.asarray(buckets), jnp.asarray(counts),
                             rcap=rcap, fill=fill)
        np.testing.assert_array_equal(np.asarray(merged), np.asarray(ref))


def test_psrs_overflow_seam_rcap_boundary():
    """End-to-end regression for the rcap overflow seam: constant keys with
    v=2 land exactly n_v elements on each receiver (the global-index
    tie-break splits duplicate runs at the median), so rcap = n_v − 1 must
    raise OverflowError while n_v and n_v + 1 succeed — on both merge
    paths."""
    from repro.pems_apps import psrs_sort
    n_v, v, k = 64, 2, 2
    x = np.full(n_v * v, 7, dtype=np.int32)
    for merge_kernel in (True, False):
        with pytest.raises(OverflowError, match="rcap"):
            psrs_sort(x, v=v, k=k, rcap=n_v - 1, merge_kernel=merge_kernel)
        for rcap in (n_v, n_v + 1):
            out = psrs_sort(x, v=v, k=k, rcap=rcap,
                            merge_kernel=merge_kernel)
            np.testing.assert_array_equal(out, np.sort(x))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 7),
       st.sampled_from([8, 32, 128]))
def test_kway_merge_property(seed, v, tile):
    rng = np.random.default_rng(seed)
    cap = int(rng.integers(1, 97))
    rcap = int(rng.integers(1, 2 * v * cap + 1))
    kind = ["random", "dups", "presorted"][seed % 3]
    buckets, counts = _merge_case(v, cap, np.int32, kind, rng=rng)
    fill = np.iinfo(np.int32).max
    merged, total, over = kway_merge(
        jnp.asarray(buckets), jnp.asarray(counts), rcap=rcap, tile=tile,
        fill=fill, use_kernel=False)
    ref = kway_merge_ref(jnp.asarray(buckets), jnp.asarray(counts),
                         rcap=rcap, fill=fill)
    np.testing.assert_array_equal(np.asarray(merged), np.asarray(ref))
    assert int(total) == int(counts.sum())
    assert bool(over) == (int(counts.sum()) > rcap)


# cap values: one index level (≤128 lanes), two, three, and padded tails.
FENCE_CAPS = [1, 17, 127, 128, 129, 128 * 128, 128 * 128 + 1]
U32_MAX = 0xFFFFFFFF


def _fence_rows(cap, rng):
    """Ascending uint32 rows: random, heavy duplicates, all fill, and a
    valid prefix followed by fill lanes."""
    rand = np.sort(rng.integers(0, 2**32, size=cap, dtype=np.uint64)
                   ).astype(np.uint32)
    dups = np.sort(rng.integers(0, 3, size=cap)).astype(np.uint32) + 7
    fill = np.full(cap, U32_MAX, np.uint32)
    tail = rand.copy()
    tail[cap // 2:] = U32_MAX
    return np.stack([rand, dups, fill, tail])


@pytest.mark.parametrize("cap", FENCE_CAPS)
@pytest.mark.parametrize("side", ["left", "right"])
def test_fence_index_counts_match_searchsorted(cap, side):
    """Per-row counts through the fence index equal np.searchsorted
    exactly: #{x < q} for side="left", #{x ≤ q} for side="right"."""
    rng = np.random.default_rng(cap)
    rows = _fence_rows(cap, rng)
    q = np.concatenate([
        [0, U32_MAX, U32_MAX - 1, 6, 7, 8, 9, 10],
        rows[0][::max(1, cap // 9)], rows[0][::max(1, cap // 9)] + 1,
        rng.integers(0, 2**32, size=40, dtype=np.uint64),
    ]).astype(np.uint32)
    index = kway_merge_ops._fence_index(jnp.asarray(rows))
    if side == "left":
        got = kway_merge_ops._count_lt(index, jnp.asarray(q))
    else:
        got = kway_merge_ops._count_le(index, jnp.asarray(q), cap)
    want = np.stack([np.searchsorted(r, q, side=side) for r in rows])
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("cap", FENCE_CAPS)
@pytest.mark.parametrize("kind", ["random", "dups", "fillmax", "presorted"])
def test_exact_starts_match_binary_search_oracle(cap, kind):
    """The splitter search's starts equal the scalar binary search's
    (``exact_starts_ref``) element for element, every rank of a tile grid
    including the all-fill tail."""
    v, tile = 3, 8
    buckets, counts = _merge_case(v, cap, np.int32, kind,
                                  rng=np.random.default_rng(cap + 1))
    lane = np.arange(cap)
    masked = np.where(lane[None, :] < counts[:, None], buckets,
                      np.iinfo(np.int32).max)
    rows = kway_merge_ops._to_biased_u32(jnp.asarray(masked))
    ranks = jnp.minimum(jnp.arange(-(-2 * v * cap // tile) + 1) * tile,
                        v * cap)
    got = kway_merge_ops._exact_starts(kway_merge_ops._fence_index(rows),
                                       ranks, cap)
    want = exact_starts_ref(rows, ranks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got).sum(axis=1),
                                  np.asarray(ranks))


def _compact_case(kind, v, tile, rng):
    """Masked ``[v, cap]`` rows (ascending, fill past the counts) and the
    merge's ``rcap`` for one compaction edge case."""
    fill = np.iinfo(np.int32).max
    cap = 2 * tile
    rcap = v * cap
    if kind == "zero_windows":    # disjoint key ranges: most windows empty
        rows = np.sort(rng.integers(0, 1000, size=(v, cap)), axis=1)
        rows = rows + 1000 * np.arange(v)[:, None]
    elif kind == "whole_tile":    # bucket 0 below the rest: whole tiles
        cap = 3 * tile
        rows = np.sort(rng.integers(10, 2**20, size=(v, cap)), axis=1)
        rows[0] = np.arange(cap)
        rcap = v * cap
    elif kind == "partial_last":  # n_all < G·tile: the last tile is short
        cap = tile + tile // 2 + 1
        rows = np.sort(rng.integers(-2**31, fill, size=(v, cap)), axis=1)
        rcap = v * cap + 3
    elif kind == "ends_on_last_lane":   # interleaved full rows
        rows = np.sort(rng.integers(-50, 50, size=(v, cap)), axis=1)
    elif kind == "all_fill":      # every row but the last empty
        rows = np.full((v, cap), fill)
        rows[-1, :cap // 3] = np.arange(cap // 3)
    else:                         # one key duplicated across every bucket
        rows = np.full((v, cap), 7)
    return rows.astype(np.int32), rcap


@pytest.mark.parametrize("kind", ["zero_windows", "whole_tile",
                                  "partial_last", "ends_on_last_lane",
                                  "all_fill", "one_key"])
@pytest.mark.parametrize("tile", [8, 256])
@pytest.mark.parametrize("v", [1, 3, 16])
def test_compact_tiles_match_lane_gather_oracle(kind, v, tile):
    """The chip's compaction (block windows) equals the searchsorted lane
    gather (``compact_tiles_ref``) lane for lane, on the exact splitters'
    starts: empty windows, a bucket filling whole tiles, a short last
    tile, windows ending on a row's last lane, all-fill rows, one key
    everywhere."""
    rows, rcap = _compact_case(kind, v, tile, np.random.default_rng(v * tile))
    fill = np.iinfo(np.int32).max
    v, cap = rows.shape
    G = -(-rcap // tile)
    ranks = jnp.minimum(jnp.arange(G + 1) * tile, v * cap)

    @jax.jit
    def both(rows):
        index = kway_merge_ops._fence_index(
            kway_merge_ops._to_biased_u32(rows))
        starts = kway_merge_ops._exact_starts(index, ranks, cap)
        got = kway_merge_ops._compact_blocks(index, starts, tile,
                                             jnp.int32(fill))
        return starts, got, compact_tiles_ref(rows, starts, tile, fill)

    starts, got, want = both(jnp.asarray(rows))
    if kind == "ends_on_last_lane":
        st = np.asarray(starts)
        assert ((st[1:] == cap) & (st[1:] > st[:-1])).any()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_psrs_bit_identical_across_merge_kernel():
    """psrs_sort with the tiled merge kernel vs the dense re-sort stage must
    agree bit-for-bit, across merge_tile widths."""
    from repro.pems_apps import psrs_sort
    x = RNG.integers(-2**30, 2**30, size=1024, dtype=np.int32)
    base = psrs_sort(x, v=8, k=2, merge_kernel=False)
    np.testing.assert_array_equal(base, np.sort(x))
    for tile in (16, 256, 1024):
        on = psrs_sort(x, v=8, k=2, merge_kernel=True, merge_tile=tile)
        np.testing.assert_array_equal(on, base)


# --------------------------------------------------------------------------- #
# PSRS with the bitonic kernel as the local sort                               #
# --------------------------------------------------------------------------- #

def test_psrs_with_bitonic_local_sort():
    from repro.pems_apps import psrs_sort
    import functools
    x = RNG.integers(-2**30, 2**30, size=512, dtype=np.int32)
    out = psrs_sort(
        x, v=4, k=2,
        local_sort=functools.partial(bitonic_sort, interpret=True),
    )
    np.testing.assert_array_equal(out, np.sort(x))


def test_psrs_default_local_sort_is_bitonic_kernel():
    """With use_kernel=True (default) the local sort resolves to the bitonic
    kernel wrapper; use_kernel=False keeps jnp.sort — both bit-identical."""
    from repro.pems_apps import psrs_sort
    x = RNG.integers(-2**31, 2**31 - 1, size=2048, dtype=np.int32)
    on = psrs_sort(x, v=4, k=2)
    off = psrs_sort(x, v=4, k=2, use_kernel=False)
    np.testing.assert_array_equal(on, off)
    np.testing.assert_array_equal(on, np.sort(x))
