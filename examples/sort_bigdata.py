"""Out-of-core sorting: PSRS over a context store larger than the device.

The store is put on the ``memmap`` backing tier — the full ``v·mu`` context
population lives in a file on disk, and only each round's ``k·mu`` is ever
device-resident.  ``DEVICE_CAP_BYTES`` enforces the budget: the population is
more than 4x the cap, so the in-memory path physically could not run under
it, yet the sort is bit-identical to the all-in-memory run.  The ``async``
driver's prefetch thread overlaps each round's disk/PCIe swap-in with the
previous round's compute (thesis §5.1).

With ``--io-driver`` the sort additionally runs on the ``file`` tier — the
same backing file reached through the :mod:`repro.io` async engine
(``buffered`` page-cached pread/pwrite, ``odirect`` page-cache-bypassing
O_DIRECT, or the ``mmap`` adapter), printing the engine's measured queue
depth, read+write overlap events, and syscall-level byte counts.

With ``--inject-faults`` the sort also demonstrates the fault-tolerance
layer: a run through the deterministic fault-injecting driver (seeded EIO
bursts + latency spikes, absorbed by the engine's bounded retries), then a
genuine ``kill -9`` mid-stage followed by a resume from the durable
superstep cursor — bit-identical to an uninterrupted run.

    PYTHONPATH=src python examples/sort_bigdata.py
    PYTHONPATH=src python examples/sort_bigdata.py --io-driver odirect
    PYTHONPATH=src python examples/sort_bigdata.py --io-driver all
    PYTHONPATH=src python examples/sort_bigdata.py --inject-faults
"""

import argparse
import os
import signal
import subprocess
import sys
import tempfile
import textwrap
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.pems_apps import psrs_run_recoverable, psrs_sort

ap = argparse.ArgumentParser()
ap.add_argument("--io-driver", default=None,
                choices=("buffered", "odirect", "mmap", "all"),
                help="also sort on tier='file' with this repro.io driver "
                     "('all' sweeps the three)")
ap.add_argument("--io-queue-depth", type=int, default=8)
ap.add_argument("--inject-faults", action="store_true",
                help="demonstrate the fault-tolerance layer: survive seeded "
                     "EIO bursts via engine retries, then kill -9 the sort "
                     "mid-stage and resume it bit-identically")
args = ap.parse_args()

n = 1 << 20
v, k = 16, 1   # k=1: the async tier keeps 3·k·mu in flight, capped below
rng = np.random.default_rng(1)
data = rng.integers(-2**31, 2**31 - 1, size=n, dtype=np.int32)
want = np.sort(data)
enable_compile_cache()

# The kill -9 leg's child runs first, before this process touches a device:
# an accelerator belongs to one process at a time, so a child started by a
# parent that already holds it could not get it.
crash_dir = crash_exit = None
if args.inject_faults:
    crash_dir = tempfile.TemporaryDirectory()
    child = textwrap.dedent(f"""
        import sys
        import numpy as np
        from repro.pems_apps import psrs_run_recoverable
        rng = np.random.default_rng(1)
        data = rng.integers(-2**31, 2**31 - 1, size={n}, dtype=np.int32)
        psrs_run_recoverable(data, v={v}, k=2, state_dir=sys.argv[1],
                             io_driver="buffered",
                             crash_in_stage="merge")
    """)
    r = subprocess.run(
        [sys.executable, "-c", child, os.path.join(crash_dir.name, "state")],
        capture_output=True, text=True, timeout=600)
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr[-2000:])
    crash_exit = r.returncode

# All-in-memory reference (the seed path, tier="device").
t0 = time.perf_counter()
ref, pems_ref = psrs_sort(data, v=v, k=k, driver="async", return_pems=True)
t_ref = time.perf_counter() - t0
assert (ref == want).all()
store_bytes = pems_ref.cfg.v * pems_ref.layout.mu_bytes

# Device-memory cap: the k resident contexts fit, the population does not.
DEVICE_CAP_BYTES = store_bytes // 4 - 1
print(f"context store : {store_bytes / 1e6:8.1f} MB (v={v}, mu="
      f"{pems_ref.layout.mu_bytes / 1e6:.1f} MB)")
print(f"device cap    : {DEVICE_CAP_BYTES / 1e6:8.1f} MB "
      f"(store is {store_bytes / DEVICE_CAP_BYTES:.1f}x larger)\n")

print(f"{'tier':8s} {'driver':10s} {'wall_s':>7s} {'disk_read':>12s} "
      f"{'disk_write':>12s} {'overlap':>8s}")
print(f"{'device':8s} {'async':10s} {t_ref:7.2f} {'-':>12s} {'-':>12s} "
      f"{'-':>8s}")

with tempfile.TemporaryDirectory() as td:
    for driver in ("explicit", "async"):
        t0 = time.perf_counter()
        out, pems = psrs_sort(
            data, v=v, k=k, driver=driver,
            tier="memmap", backing_path=os.path.join(td, f"{driver}.bin"),
            device_cap_bytes=DEVICE_CAP_BYTES,
            return_pems=True,
        )
        dt = time.perf_counter() - t0
        assert (out == ref).all(), "out-of-core sort diverged from in-memory"
        led, ts = pems.ledger, pems.tier_stats
        print(f"{'memmap':8s} {driver:10s} {dt:7.2f} "
              f"{led.disk_read_bytes:12,} {led.disk_write_bytes:12,} "
              f"{ts.overlap_fraction:8.2%}")

    if args.io_driver is not None:
        io_drivers = (("buffered", "odirect", "mmap")
                      if args.io_driver == "all" else (args.io_driver,))
        print(f"\nfile tier (repro.io engine, queue depth "
              f"{args.io_queue_depth}):")
        print(f"{'io_driver':10s} {'driver':10s} {'wall_s':>7s} "
              f"{'syscall_rd':>12s} {'syscall_wr':>12s} {'overlap':>8s} "
              f"{'depth':>5s} {'rw_ovl':>6s}")
        for io_driver in io_drivers:
            for driver in ("explicit", "async"):
                t0 = time.perf_counter()
                out, pems = psrs_sort(
                    data, v=v, k=k, driver=driver, tier="file",
                    io_driver=io_driver,
                    io_queue_depth=args.io_queue_depth,
                    backing_path=os.path.join(
                        td, f"{io_driver}-{driver}.bin"),
                    device_cap_bytes=DEVICE_CAP_BYTES,
                    return_pems=True,
                )
                dt = time.perf_counter() - t0
                assert (out == ref).all(), \
                    "file-tier sort diverged from in-memory"
                led, ts = pems.ledger, pems.tier_stats
                print(f"{io_driver:10s} {driver:10s} {dt:7.2f} "
                      f"{led.syscall_read_bytes:12,} "
                      f"{led.syscall_write_bytes:12,} "
                      f"{ts.overlap_fraction:8.2%} {ts.max_queue_depth:5d} "
                      f"{ts.rw_overlap_events:6d}")

print("\nout-of-core result bit-identical to the in-memory run")

if args.inject_faults:
    SPEC = "seed=5;eio@p0.03:x2;lat@p0.02:0.001"
    print(f"\nfault tolerance (fault_spec={SPEC!r}):")
    with tempfile.TemporaryDirectory() as td:
        t0 = time.perf_counter()
        out, pems = psrs_sort(
            data, v=v, k=2, driver="async", tier="file",
            io_driver="faulty:buffered", fault_spec=SPEC, io_retries=4,
            checksums=True, io_queue_depth=args.io_queue_depth,
            backing_path=os.path.join(td, "faulty.bin"), return_pems=True)
        dt = time.perf_counter() - t0
        assert (out == want).all(), "faulted sort diverged"
        inj, ts = pems.backing.file.injected, pems.tier_stats
        print(f"  survived seeded faults in {dt:.2f}s: injected "
              f"eio={inj['eio']} lat={inj['lat']}; engine retries="
              f"{ts.retries} backoff={ts.backoff_s * 1e3:.1f}ms "
              f"permanent_errors={ts.permanent_errors}")

        # Resume the child killed -9 mid-stage at start-up from its durable
        # superstep cursor.
        state = os.path.join(crash_dir.name, "state")
        print(f"  child killed -9 mid-'merge' (exit {crash_exit}); "
              "cursor + checksummed backing left behind — resuming ...")
        t0 = time.perf_counter()
        out2 = psrs_run_recoverable(data, v=v, k=2, state_dir=state,
                                    io_driver="buffered")
        assert (np.asarray(out2) == want).all(), "resumed sort diverged"
        print(f"  resumed from the superstep cursor in "
              f"{time.perf_counter() - t0:.2f}s; output bit-identical to "
              "the uninterrupted run")
    crash_dir.cleanup()

print("\nPEMS2 direct vs PEMS1 indirect delivery (same sort, device tier):")
for mode in ("direct", "indirect"):
    t0 = time.perf_counter()
    out, pems = psrs_sort(data, v=16, k=4, mode=mode, return_pems=True)
    dt = time.perf_counter() - t0
    led = pems.ledger
    print(f"  {mode:9s} wall={dt:6.2f}s io={led.io_total:14,} "
          f"disk={led.disk_space:14,}")
