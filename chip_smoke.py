#!/usr/bin/env python3
"""Chip smoke run: PSRS end to end on a TPU, checked against ``np.sort``.

    python chip_smoke.py              # one chip: device tier, then file tier
    python chip_smoke.py --chips 4    # four chips: the P=4 mesh phase only

Phases (one process, so one owner of the chip):

* device tier — ``psrs_sort(keys, v=16, k=4)`` on ``n = 2^25`` int32 keys,
  whole program jitted.  The same program is lowered and compiled first:
  its compile time is reported as set-up, and its compiled text must hold a
  ``tpu_custom_call`` for each Pallas kernel the path uses (delivery,
  k-way merge, and the bitonic local sort where the size rule picks it).
* file tier — ``psrs_sort(keys, v=16, k=1, driver="async", tier="file",
  io_driver="buffered")`` on ``n = 2^24`` keys with ``device_cap_bytes`` a
  quarter of the context store, so the population on disk is 4x the
  device budget.
* mesh (``--chips 4`` only) — ``psrs_sort(keys, v=16, k=1, P=4, mesh=...)``
  on ``n = 2^26`` keys over a 4-device ``vp`` mesh; each device must hold a
  quarter of the context store.

Each phase compares its output with ``np.sort`` of the same seeded keys
and fails the run on any difference.  Times are single smoke-run wall
times, not measurements.  The last line of standard output is one JSON
object naming the device.  With no TPU the script exits 1 before any
phase runs; without the repository's ``src`` beside it, it exits 2.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DEVICE_LOG2_N = 25
FILE_LOG2_N = 24
MESH_LOG2_N = 26
V = 16


def make_keys(n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(-2**31, 2**31 - 1, size=n, dtype=np.int32,
                        endpoint=True)


def _check(name: str, out, keys: np.ndarray) -> None:
    want = np.sort(keys)
    out = np.asarray(out)
    if out.shape != want.shape or not np.array_equal(out, want):
        bad = (np.flatnonzero(out != want)[:5] if out.shape == want.shape
               else out.shape)
        raise AssertionError(f"{name}: result differs from np.sort ({bad})")
    print(f"{name}: result equals np.sort of {keys.size} seeded keys")


def _peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else str(peak)


def _kernels_in(compiled_text: str, names) -> dict:
    """For each kernel name, whether a ``tpu_custom_call`` line of the
    compiled program carries it."""
    calls = [ln.strip() for ln in compiled_text.splitlines()
             if "tpu_custom_call" in ln]
    return {nm: any(re.match(rf"(ROOT )?%\w*{nm}\w*(\.\d+)? = ", ln)
                    for ln in calls) for nm in names}


def device_phase(keys: np.ndarray, v: int = V, k: int = 4) -> None:
    import jax
    from repro.kernels.alltoallv_deliver import uses_pallas
    from repro.kernels.bitonic_sort.ops import KERNEL_MAX_N, sort_path
    from repro.pems_apps import psrs_plan, psrs_sort

    n_v = keys.size // v
    path = sort_path(n_v)
    print(f"device: n={keys.size} v={v} k={k} n_v={n_v} local sort path="
          f"{path} (bitonic kernel up to rows of {KERNEL_MAX_N})")

    # The program psrs_sort jits, lowered here so its compile is set-up
    # and its compiled text can be inspected.
    _, load, steps, extract = psrs_plan(v, n_v, k=k)

    def program(data):
        store = load(data)
        for _, step in steps:
            store = step(store)
        return extract(store)

    data = jax.numpy.asarray(keys.reshape(v, n_v))
    t0 = time.perf_counter()
    compiled = jax.jit(program).lower(data).compile()
    print(f"device: compile (set-up) {time.perf_counter() - t0:.3f} s")
    ma = compiled.memory_analysis()
    if ma is not None:
        print(f"device: compiled temp bytes {ma.temp_size_in_bytes} "
              f"argument bytes {ma.argument_size_in_bytes} output bytes "
              f"{ma.output_size_in_bytes}")
    if uses_pallas():
        want = ["alltoallv_deliver", "kway_merge"]
        if path == "bitonic_kernel":
            want.append("bitonic_sort")
        found = _kernels_in(compiled.as_text(), want)
        print(f"device: tpu_custom_call per kernel {found}")
        missing = [nm for nm, ok in found.items() if not ok]
        if missing:
            raise AssertionError(
                f"device: compiled program lacks kernels {missing}")

    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(data))
    print(f"device: run of the compiled program {time.perf_counter() - t0:.3f} s")
    del out, compiled, data

    t0 = time.perf_counter()
    out = psrs_sort(keys, v=v, k=k)
    print(f"device: psrs_sort wall (own trace + compile-cache lookup + run) "
          f"{time.perf_counter() - t0:.3f} s")
    _check("device", out, keys)
    print(f"device: peak_bytes_in_use {_peak_bytes(jax.devices()[0])}")


def file_phase(keys: np.ndarray, v: int = V, k: int = 1,
               workdir: str | None = None) -> None:
    import jax
    from repro.pems_apps import psrs_plan, psrs_sort

    n_v = keys.size // v
    pems0, *_ = psrs_plan(v, n_v, k=k)
    store_bytes = v * pems0.layout.mu_bytes
    cap = store_bytes // 4
    print(f"file: n={keys.size} v={v} k={k} store bytes {store_bytes} "
          f"device_cap_bytes {cap}")
    with tempfile.TemporaryDirectory(dir=workdir) as td:
        t0 = time.perf_counter()
        out, pems = psrs_sort(
            keys, v=v, k=k, driver="async", tier="file",
            io_driver="buffered", backing_path=os.path.join(td, "ctx.bin"),
            device_cap_bytes=cap, return_pems=True)
        print(f"file: psrs_sort wall (stage compiles included) "
              f"{time.perf_counter() - t0:.3f} s")
    _check("file", out, keys)
    led, ts = pems.ledger, pems.tier_stats
    print(f"file: disk_read_bytes {led.disk_read_bytes} disk_write_bytes "
          f"{led.disk_write_bytes} overlap_fraction {ts.overlap_fraction}")
    print(f"file: peak_bytes_in_use {_peak_bytes(jax.devices()[0])}")


def mesh_phase(keys: np.ndarray, v: int = V, P: int = 4) -> None:
    import jax
    from repro.launch.mesh import make_mesh_auto
    from repro.pems_apps import psrs_plan, psrs_sort

    devs = jax.devices()[:P]
    if len(devs) < P:
        raise AssertionError(f"mesh: needs {P} devices, found {len(devs)}")
    mesh = make_mesh_auto((P,), ("vp",), devices=devs)
    n_v = keys.size // v
    print(f"mesh: n={keys.size} v={v} k=1 P={P} n_v={n_v}")

    # Where the store lives: one load, inspected, then freed.
    _, load, _, _ = psrs_plan(v, n_v, k=1, P=P, mesh=mesh)
    store = load(jax.numpy.asarray(keys.reshape(v, n_v)))
    total = store.data.nbytes
    shards = {str(s.device): s.data.nbytes
              for s in store.data.addressable_shards}
    print(f"mesh: store bytes {total} per device {json.dumps(shards)}")
    if sorted(shards.values()) != [total // P] * P:
        raise AssertionError(f"mesh: store is not split in {P} quarters")
    del store

    t0 = time.perf_counter()
    out = psrs_sort(keys, v=v, k=1, P=P, mesh=mesh)
    print(f"mesh: psrs_sort wall (compiles included) "
          f"{time.perf_counter() - t0:.3f} s")
    _check("mesh", out, keys)
    for d in devs:
        print(f"mesh: {d} peak_bytes_in_use {_peak_bytes(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the P=4 mesh phase only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        import repro.pems_apps  # noqa: F401
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    print(f"chip_smoke: {len(devices)} x {dev.device_kind}, compile cache "
          f"{enable_compile_cache()}")

    t0 = time.perf_counter()
    if args.chips == 4:
        keys = make_keys(1 << MESH_LOG2_N, args.seed)
        print(f"keys (set-up) {time.perf_counter() - t0:.3f} s")
        mesh_phase(keys)
    else:
        keys = make_keys(1 << DEVICE_LOG2_N, args.seed)
        print(f"keys (set-up) {time.perf_counter() - t0:.3f} s")
        device_phase(keys)
        del keys
        file_phase(make_keys(1 << FILE_LOG2_N, args.seed + 1))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
