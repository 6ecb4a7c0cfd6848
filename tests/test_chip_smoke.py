"""CPU rehearsal of ``chip_smoke.py``: its phase functions at tiny sizes,
bit-identical to ``np.sort``, and its refusal to run without a TPU or
without the repository beside it."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "JAX_PLATFORMS": "cpu"}
    for key in ("HOME", "TMPDIR"):
        if key in os.environ:
            env[key] = os.environ[key]
    env.update(extra)
    return env


def test_device_phase_tiny(smoke, capsys):
    smoke.device_phase(smoke.make_keys(1 << 12, 3), v=4, k=2)
    out = capsys.readouterr().out
    assert "device: result equals np.sort of 4096 seeded keys" in out
    assert "local sort path=jnp.sort" in out       # CPU: no Pallas kernels


def test_file_phase_tiny(smoke, capsys, tmp_path):
    smoke.file_phase(smoke.make_keys(1 << 12, 4), v=16, k=1,
                     workdir=str(tmp_path))
    out = capsys.readouterr().out
    assert "file: result equals np.sort of 4096 seeded keys" in out
    assert "overlap_fraction" in out
    assert list(tmp_path.iterdir()) == []           # backing file removed


def test_mesh_phase_four_virtual_devices():
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("cs", {SCRIPT!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        cs.mesh_phase(cs.make_keys(1 << 12, 5), v=8, P=4)
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=REPO,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "mesh: result equals np.sort of 4096 seeded keys" in r.stdout
    line = next(ln for ln in r.stdout.splitlines()
                if ln.startswith("mesh: store bytes"))
    total = int(line.split()[3])
    per_dev = json.loads(line.split(" per device ", 1)[1])
    assert sorted(per_dev.values()) == [total // 4] * 4


def test_main_refuses_cpu():
    r = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                       text=True, timeout=300, cwd=REPO, env=_env())
    assert r.returncode == 1
    assert r.stdout == ""
    assert "no TPU" in r.stderr


def test_script_alone_fails(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    r = subprocess.run([sys.executable, str(lone)], capture_output=True,
                       text=True, timeout=300, cwd=str(tmp_path), env=_env())
    assert r.returncode not in (0, None)
    for ln in r.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(ln)
