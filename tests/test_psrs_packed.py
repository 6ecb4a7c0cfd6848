"""PSRS on its packed context: one sort of uniform, NPB IS, all-equal and
pre-sorted keys on every tier, driver, process count and delivery mode,
and the receive overflow it still reports.  The layout and its accounting
are tested in ``test_psrs_layout.py``."""

import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.pems_apps import psrs, psrs_sort

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_N, _V, _K = 512, 4, 2


def _key_sets(n=_N, seed=7):
    """Uniform, NPB IS (bell shaped, many duplicates), all-equal and
    pre-sorted keys."""
    rng = np.random.default_rng(seed)
    uniform = rng.integers(-2**31, 2**31 - 1, size=n, dtype=np.int32)
    max_key = 1 << 11
    npb = np.floor(max_key / 4 * rng.random((4, n)).sum(0)).astype(np.int32)
    return {"uniform": uniform, "npb_is": npb,
            "all_equal": np.full(n, -5, np.int32),
            "presorted": np.sort(uniform)}


_CASES = [(tier, driver, P, mode)
          for tier in ("device", "host", "memmap", "file")
          for driver in ("explicit", "sliced", "async")
          for P in (1, 2) if not (tier == "device" and P == 2)
          for mode in ("direct", "indirect")]


@pytest.mark.parametrize("tier,driver,P,mode", _CASES,
                         ids=["-".join(map(str, c)) for c in _CASES])
def test_psrs_sorts_every_key_set_on_every_path(tmp_path, tier, driver, P,
                                                mode):
    """Bit-identical to np.sort on each tier × driver × P × mode (the
    device tier's P = 2 mesh runs in a subprocess below).  Each case builds
    ``psrs_sort``'s program once and runs every key set through it, so its
    stages compile once."""
    n_v = _N // _V
    kw = {} if tier == "device" else {"backing_path": str(tmp_path / "b")}
    _, program, _ = psrs._build(_V, _K, n_v, None, driver, mode, None,
                                tier=tier, P=P, **kw)
    for name, keys in _key_sets().items():
        blocks = keys.reshape(_V, n_v)
        result, total, over = program(
            jnp.asarray(blocks) if tier == "device" else blocks)
        result, total = np.asarray(result), np.asarray(total)[:, 0]
        assert not np.asarray(over).any(), name
        out = np.concatenate([result[i, :total[i]] for i in range(_V)])
        np.testing.assert_array_equal(out, np.sort(keys), err_msg=name)


_MESH = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import json, sys
    import numpy as np
    sys.path.insert(0, os.path.join(sys.argv[1], "tests"))
    from test_psrs_packed import _key_sets
    from repro.launch.mesh import make_mesh_auto
    from repro.pems_apps import psrs_sort

    mesh = make_mesh_auto((2,), ("vp",))
    runs = 0
    for i, (name, keys) in enumerate(_key_sets().items()):
        # Each key set once; the modes and chunkings two sets each.
        mode, alpha = [("direct", None), ("indirect", 1)][i % 2]
        out = psrs_sort(keys, v=4, k=1, P=2, mesh=mesh, mode=mode,
                        alpha=alpha)
        assert np.array_equal(out, np.sort(keys)), (name, mode)
        runs += 1
    print(json.dumps({"runs": runs}))
""")


def test_psrs_sorts_every_key_set_on_the_device_mesh_subprocess():
    """P = 2 over a CPU mesh: the staged tiles are assembled, shipped by
    all_to_all (whole and α-chunked) and packed on arrival."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(_ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", _MESH, _ROOT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert json.loads(r.stdout.strip().splitlines()[-1]) == {"runs": 4}


@pytest.mark.parametrize("tier", ["device", "file"])
def test_receive_overflow_still_raises(tmp_path, tier):
    """A context that receives more than rcap keys raises, whatever the
    tier: the offsets count every key even where the field drops them."""
    keys = np.sort(_key_sets()["uniform"])[::-1].copy()
    kw = {} if tier == "device" else {"backing_path": str(tmp_path / "b")}
    with pytest.raises(OverflowError):
        psrs_sort(keys, v=_V, k=1, tier=tier, rcap=_N // _V // 2, **kw)
