"""Behavioural tests for the PEMS core: executor rounds, drivers, collectives
vs numpy oracles, and multi-real-processor (P>1) equivalence via subprocess."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.core import ContextLayout, Pems, PemsConfig


def make_layout(v, omega, n=16):
    return (
        ContextLayout()
        .add("data", (n,), jnp.int32)
        .add("acc", (1,), jnp.int32)
        .add("send", (v, omega), jnp.int32)
        .add("scnt", (v,), jnp.int32)
        .add("recv", (v, omega), jnp.int32)
        .add("rcnt", (v,), jnp.int32)
    )


def fill_send(rho, ctx, v, omega):
    msgs = (rho * 1000 + jnp.arange(v, dtype=jnp.int32))[:, None]
    msgs = msgs * jnp.ones((1, omega), jnp.int32) + jnp.arange(omega, dtype=jnp.int32)
    cnt = (rho + jnp.arange(v, dtype=jnp.int32)) % omega + 1
    return ctx.set("send", msgs).set("scnt", cnt)


# --------------------------------------------------------------------------- #
# Superstep engine                                                             #
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("driver", ["explicit", "sliced", "async"])
@pytest.mark.parametrize("v,k", [(4, 1), (8, 2), (8, 4), (12, 3)])
def test_superstep_rounds_all_drivers(v, k, driver):
    lo = make_layout(v, 4)
    pems = Pems(PemsConfig(v=v, k=k, driver=driver), lo)
    store = pems.init(lambda rho: {"data": rho * jnp.ones(16, jnp.int32)})

    def step(rho, ctx):
        return ctx.set("acc", ctx.get("data")[:1] * 2 + rho)

    store = pems.superstep(store, step, reads=["data"], writes=["acc"])
    acc = np.asarray(store.field("acc"))[:, 0]
    np.testing.assert_array_equal(acc, np.arange(v) * 3)


def test_sliced_driver_only_writes_declared_fields():
    v = 4
    lo = make_layout(v, 4)
    pems = Pems(PemsConfig(v=v, k=2, driver="sliced"), lo)
    store = pems.init(lambda rho: {"data": rho * jnp.ones(16, jnp.int32)})

    def step(rho, ctx):
        # Tries to clobber "data", but only "acc" is declared as written.
        return ctx.set("data", jnp.zeros(16, jnp.int32)).set(
            "acc", jnp.ones(1, jnp.int32)
        )

    store = pems.superstep(store, step, reads=["data"], writes=["acc"])
    np.testing.assert_array_equal(
        np.asarray(store.field("data"))[:, 0], np.arange(v)
    )
    np.testing.assert_array_equal(np.asarray(store.field("acc"))[:, 0], 1)


def test_superstep_jits_and_is_deterministic():
    v, k = 8, 2
    lo = make_layout(v, 4)
    pems = Pems(PemsConfig(v=v, k=k), lo)

    @jax.jit
    def prog(data):
        from repro.core import ContextStore
        store = ContextStore(lo, data)
        store = pems.superstep(store, lambda rho, c: c.set("acc", rho[None]))
        return store.data

    store = pems.init()
    out1, out2 = prog(store.data), prog(store.data)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


# --------------------------------------------------------------------------- #
# Alltoallv                                                                    #
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("mode", ["direct", "indirect"])
@pytest.mark.parametrize("v,k,omega", [(4, 1, 2), (8, 2, 4), (6, 3, 8)])
def test_alltoallv_transposes_messages(v, k, omega, mode):
    lo = make_layout(v, omega)
    pems = Pems(PemsConfig(v=v, k=k), lo)
    store = pems.init()
    store = pems.superstep(store, lambda r, c: fill_send(r, c, v, omega))
    store = pems.alltoallv(store, "send", "recv", "scnt", "rcnt", mode=mode)

    S = np.asarray(store.field("send"))
    R = np.asarray(store.field("recv"))
    C = np.asarray(store.field("scnt"))
    Rc = np.asarray(store.field("rcnt"))
    np.testing.assert_array_equal(R, np.swapaxes(S, 0, 1))
    np.testing.assert_array_equal(Rc, C.T)


def test_alltoallv_direct_equals_indirect():
    v, k, omega = 8, 2, 4
    lo = make_layout(v, omega)
    a = Pems(PemsConfig(v=v, k=k), lo)
    b = Pems(PemsConfig(v=v, k=k), lo)
    sa = a.superstep(a.init(), lambda r, c: fill_send(r, c, v, omega))
    sb = b.superstep(b.init(), lambda r, c: fill_send(r, c, v, omega))
    sa = a.alltoallv(sa, "send", "recv", mode="direct")
    sb = b.alltoallv(sb, "send", "recv", mode="indirect")
    np.testing.assert_array_equal(
        np.asarray(sa.field("recv")), np.asarray(sb.field("recv"))
    )
    # ...and PEMS2 moves strictly fewer bytes (Cor 7.1.4) once ω ≳ B is not
    # required because the boundary cache charge is included:
    assert a.ledger.io_total != b.ledger.io_total


@pytest.mark.parametrize("v,k,omega", [
    (4, 1, 2), (8, 2, 4), (6, 3, 129),
    (4, 1, 1024),   # ω past the row-loop cutover: vectorised delivery path
])
def test_alltoallv_fused_equals_dense(v, k, omega):
    """The word-level kernel path (use_kernel=True, the default) is
    bit-identical to the seed dense-transpose path, payload and counts,
    and charges the same ledger events."""
    outs, ledgers = [], []
    for use_kernel in (True, False):
        lo = make_layout(v, omega)
        pems = Pems(PemsConfig(v=v, k=k), lo)
        store = pems.init()
        store = pems.superstep(store, lambda r, c: fill_send(r, c, v, omega))
        store = pems.alltoallv(store, "send", "recv", "scnt", "rcnt",
                               use_kernel=use_kernel)
        outs.append((np.asarray(store.field("recv")),
                     np.asarray(store.field("rcnt"))))
        ledgers.append(pems.ledger.io_total)
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    assert ledgers[0] == ledgers[1]


@pytest.mark.parametrize("mode", ["direct", "indirect"])
@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize("omega", [5, 1024])   # row-loop and vectorised paths
def test_alltoallv_fill_fuses_boundary_mask(mode, use_kernel, omega):
    """fill=x masks lanes past counts[s, d] during delivery (the fused
    boundary fix-up), identically on every implementation path."""
    v, k = 6, 2
    lo = make_layout(v, omega)
    pems = Pems(PemsConfig(v=v, k=k), lo)
    store = pems.init()
    store = pems.superstep(store, lambda r, c: fill_send(r, c, v, omega))
    store = pems.alltoallv(store, "send", "recv", "scnt", "rcnt",
                           mode=mode, fill=-42, use_kernel=use_kernel)
    S = np.asarray(store.field("send"))
    C = np.asarray(store.field("scnt"))
    R = np.asarray(store.field("recv"))
    lane = np.arange(omega)[None, None, :]
    want = np.where(lane < C.T[:, :, None], np.swapaxes(S, 0, 1), -42)
    np.testing.assert_array_equal(R, want)
    np.testing.assert_array_equal(np.asarray(store.field("rcnt")), C.T)


def test_alltoallv_send_recv_aliasing():
    """send == recv (in-place shuffle) must match the dense path — the
    row-loop delivery is skipped for aliased fields since it reads source
    rows after overwriting them."""
    v, k, omega = 6, 2, 4
    outs = []
    for use_kernel in (True, False):
        lo = make_layout(v, omega)
        pems = Pems(PemsConfig(v=v, k=k), lo)
        store = pems.init()
        store = pems.superstep(store, lambda r, c: fill_send(r, c, v, omega))
        S = np.asarray(store.field("send"))
        store = pems.alltoallv(store, "send", "send", use_kernel=use_kernel)
        outs.append(np.asarray(store.field("send")))
        np.testing.assert_array_equal(outs[-1], np.swapaxes(S, 0, 1))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_alltoallv_fill_requires_counts():
    lo = make_layout(4, 2)
    pems = Pems(PemsConfig(v=4), lo)
    with pytest.raises(ValueError):
        pems.alltoallv(pems.init(), "send", "recv", fill=0)


def test_field_words_roundtrip():
    """ContextStore word-level API: field_words_view/with_field_words are
    exact inverses and bit-compatible with the typed accessors."""
    from repro.core import ContextStore
    v = 4
    lo = make_layout(v, 3)
    pems = Pems(PemsConfig(v=v), lo)
    store = pems.init(
        lambda rho: {"data": rho * jnp.ones(16, jnp.int32),
                     "send": jnp.full((v, 3), -rho, jnp.int32)}
    )
    W = store.field_words_view("send")
    assert W.shape == (v, v * 3) and W.dtype == jnp.uint32
    np.testing.assert_array_equal(
        np.asarray(W).view(np.int32).reshape(v, v, 3),
        np.asarray(store.field("send")),
    )
    store2 = store.with_field_words("recv", W)
    np.testing.assert_array_equal(
        np.asarray(store2.field("recv")), np.asarray(store.field("send"))
    )
    # Other fields untouched.
    np.testing.assert_array_equal(
        np.asarray(store2.field("data")), np.asarray(store.field("data"))
    )
    with pytest.raises(TypeError):
        store.with_field_words("recv", W.astype(jnp.int32))


# --------------------------------------------------------------------------- #
# Rooted collectives vs oracles                                                #
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("root", [0, 3, 7])
def test_bcast(root):
    v = 8
    lo = ContextLayout().add("x", (5,), jnp.float32)
    pems = Pems(PemsConfig(v=v, k=2), lo)
    store = pems.init(lambda rho: {"x": jnp.full(5, rho, jnp.float32)})
    store = pems.bcast(store, "x", root=root)
    X = np.asarray(store.field("x"))
    np.testing.assert_array_equal(X, np.full((v, 5), root, np.float32))


@pytest.mark.parametrize("root", [0, 2])
def test_gather(root):
    v = 4
    lo = (ContextLayout()
          .add("x", (3,), jnp.int32)
          .add("gath", (v, 3), jnp.int32))
    pems = Pems(PemsConfig(v=v, k=1), lo)
    store = pems.init(lambda rho: {"x": rho * 10 + jnp.arange(3, dtype=jnp.int32)})
    store = pems.gather(store, "x", "gath", root=root)
    G = np.asarray(store.field("gath"))
    want = np.arange(v)[:, None] * 10 + np.arange(3)
    np.testing.assert_array_equal(G[root], want)
    # Non-root contexts untouched (zeros).
    for r in range(v):
        if r != root:
            np.testing.assert_array_equal(G[r], 0)


def test_allgather():
    v = 4
    lo = (ContextLayout()
          .add("x", (2,), jnp.int32)
          .add("gath", (v, 2), jnp.int32))
    pems = Pems(PemsConfig(v=v, k=2), lo)
    store = pems.init(lambda rho: {"x": jnp.full(2, rho, jnp.int32)})
    store = pems.allgather(store, "x", "gath")
    G = np.asarray(store.field("gath"))
    want = np.broadcast_to(np.arange(v)[:, None] * np.ones(2, int), (v, 2))
    for r in range(v):
        np.testing.assert_array_equal(G[r], want)


@pytest.mark.parametrize("op,np_op", [("add", np.sum), ("max", np.max),
                                      ("min", np.min)])
def test_reduce_ops(op, np_op):
    v, n = 8, 6
    lo = (ContextLayout()
          .add("x", (n,), jnp.float32)
          .add("out", (n,), jnp.float32))
    pems = Pems(PemsConfig(v=v, k=2), lo)
    store = pems.init(
        lambda rho: {"x": (rho + 1.0) * jnp.arange(1, n + 1, dtype=jnp.float32)}
    )
    store = pems.reduce(store, "x", "out", op=op, root=3)
    X = np.asarray(store.field("x"))
    O = np.asarray(store.field("out"))
    np.testing.assert_allclose(O[3], np_op(X, axis=0), rtol=1e-6)


def test_allreduce():
    v, n = 4, 3
    lo = (ContextLayout()
          .add("x", (n,), jnp.float32)
          .add("out", (n,), jnp.float32))
    pems = Pems(PemsConfig(v=v, k=2), lo)
    store = pems.init(lambda rho: {"x": jnp.full(n, rho + 1.0, jnp.float32)})
    store = pems.allreduce(store, "x", "out", op="add")
    O = np.asarray(store.field("out"))
    np.testing.assert_allclose(O, np.full((v, n), 10.0))


# --------------------------------------------------------------------------- #
# Property tests                                                               #
# --------------------------------------------------------------------------- #

@settings(max_examples=20, deadline=None)
@given(
    v_over_k=st.integers(1, 4),
    k=st.integers(1, 3),
    omega=st.integers(1, 8),
    seed=st.integers(0, 2**31 - 1),
)
def test_alltoallv_roundtrip_property(v_over_k, k, omega, seed):
    """alltoallv twice == identity on message payloads (transpose involution)."""
    v = v_over_k * k
    lo = make_layout(v, omega)
    pems = Pems(PemsConfig(v=v, k=k), lo)
    rng = np.random.default_rng(seed)
    M = rng.integers(0, 2**31 - 1, size=(v, v, omega), dtype=np.int32)
    store = pems.init().with_field("send", jnp.asarray(M))
    store = pems.alltoallv(store, "send", "recv")
    store = store.with_field("send", store.field("recv"))
    store = pems.alltoallv(store, "send", "recv")
    np.testing.assert_array_equal(np.asarray(store.field("recv")), M)


# --------------------------------------------------------------------------- #
# Multi-real-processor (P > 1): subprocess with fake devices                    #
# --------------------------------------------------------------------------- #

_P_GT_1 = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import Pems, PemsConfig, ContextLayout, analysis

    v, k, P, omega = 16, 2, 4, 4
    mesh = jax.make_mesh((P,), ("vp",))

    def make_lo():
        return (ContextLayout()
                .add("send", (v, omega), jnp.int32)
                .add("scnt", (v,), jnp.int32)
                .add("recv", (v, omega), jnp.int32)
                .add("rcnt", (v,), jnp.int32))

    def step(rho, ctx):
        msgs = (rho * 1000 + jnp.arange(v, dtype=jnp.int32))[:, None]
        msgs = msgs * jnp.ones((1, omega), jnp.int32)
        cnt = (rho + jnp.arange(v, dtype=jnp.int32)) % omega + 1
        return ctx.set("send", msgs).set("scnt", cnt)

    # Fused (src_proc, dst_proc)-tiled word-level route (use_kernel=True,
    # the default) vs the dense _global_transpose reference: bit-identical
    # payload, counts, and ledger for every network chunking.
    for alpha in (None, 1, 2):
        outs = []
        for use_kernel in (True, False):
            pems = Pems(PemsConfig(v=v, k=k, P=P, alpha=alpha), make_lo(),
                        mesh=mesh)
            store = pems.superstep(pems.init(), step)
            store = pems.alltoallv(store, "send", "recv", "scnt", "rcnt",
                                   fill=-7, use_kernel=use_kernel)
            outs.append((np.asarray(store.field("recv")),
                         np.asarray(store.field("rcnt")),
                         pems.ledger.io_total, pems.ledger.network_rounds))
        np.testing.assert_array_equal(outs[0][0], outs[1][0])
        np.testing.assert_array_equal(outs[0][1], outs[1][1])
        assert outs[0][2] == outs[1][2]
        S = np.asarray(store.field("send"))
        C = np.asarray(store.field("scnt"))
        lane = np.arange(omega)[None, None, :]
        want = np.where(lane < C.T[:, :, None], np.swapaxes(S, 0, 1), -7)
        np.testing.assert_array_equal(outs[0][0], want)
        np.testing.assert_array_equal(outs[0][1], C.T)
        assert outs[0][3] == analysis.pems2_alltoallv_par_network_rounds(
            v, P, k, alpha)

    # Plain transpose (no counts) through the fused mesh route + bcast.
    pems = Pems(PemsConfig(v=v, k=k, P=P), make_lo(), mesh=mesh)
    store = pems.superstep(pems.init(), step)
    store = pems.alltoallv(store, "send", "recv")
    S = np.asarray(store.field("send"))
    R = np.asarray(store.field("recv"))
    np.testing.assert_array_equal(R, np.swapaxes(S, 0, 1))

    store = pems.bcast(store, "recv", root=5)
    R2 = np.asarray(store.field("recv"))
    np.testing.assert_array_equal(R2, np.broadcast_to(R[5], R2.shape))
    print("MULTIPROC_OK")
""")


def test_multiprocessor_alltoallv_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", _P_GT_1],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             # Without an explicit platform, jax probes for TPUs via the
             # cloud metadata URL and stalls for minutes off-cloud.
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "MULTIPROC_OK" in r.stdout, r.stderr[-3000:]
