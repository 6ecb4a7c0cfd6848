"""Quickstart: sort data far bigger than "memory" with a BSP algorithm.

The PSRS sorting algorithm is written for v=16 virtual processors; the PEMS2
executor runs it with only k=4 contexts resident at a time, delivering
messages directly into destination contexts (thesis §6.2) and metering every
byte of simulated external-memory traffic.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.pems_apps import psrs_sort

enable_compile_cache()

n = 1 << 20
rng = np.random.default_rng(0)
data = rng.integers(-2**31, 2**31 - 1, size=n, dtype=np.int32)

out, pems = psrs_sort(data, v=16, k=4, return_pems=True)
assert (out == np.sort(data)).all()

led = pems.ledger
print(f"sorted {n:,} int32s with v={pems.cfg.v} virtual processors, "
      f"k={pems.cfg.k} resident")
print(f"  context size mu        : {pems.layout.mu_bytes:,} bytes")
print(f"  swap I/O               : {led.swap_total:,} bytes")
print(f"  direct message delivery: {led.msg_direct:,} bytes")
print(f"  indirect (late) deliver: {led.msg_indirect:,} bytes")
print(f"  external-memory footprint: {led.disk_space:,} bytes "
      f"(PEMS1 would need {led.disk_space + pems.cfg.v * pems.layout.mu_bytes:,})")
print(f"  superstep barriers     : {led.supersteps}")
