"""Keys of the NAS Parallel Benchmarks Integer Sort (NPB IS).

NPB IS draws each of ``TOTAL_KEYS`` keys as
``floor((MAX_KEY / 4) * (r1 + r2 + r3 + r4))`` with the ``r`` uniform in
[0, 1): a bell-shaped distribution over [0, MAX_KEY) with many duplicate
keys.  The class sets the two sizes (class A: 2^23 keys below 2^19).

The uniforms here come from numpy's PCG64 seeded by ``(seed, index)``,
not from NPB's ``randlc`` stream, so the keys follow NPB's distribution
but are not NPB's exact key sequence.
"""

from __future__ import annotations

import numpy as np

# Uniforms drawn per pass: bounds the float64 scratch at 4 x 8 MiB.
_CHUNK = 1 << 20


def generate(params: dict, seed: int, index: int) -> np.ndarray:
    """Key set ``index`` of the mix ``params`` for ``seed``: int32 keys in
    ``[0, 2**max_key_log2)``; the same arguments give the same keys."""
    n = 1 << int(params["total_keys_log2"])
    max_key = 1 << int(params["max_key_log2"])
    rng = np.random.default_rng([int(seed) % (1 << 64), int(index)])
    keys = np.empty(n, np.int32)
    for i in range(0, n, _CHUNK):
        m = min(_CHUNK, n - i)
        r = rng.random((4, m)).sum(axis=0)
        keys[i:i + m] = np.floor(r * (max_key / 4)).astype(np.int32)
    return keys
