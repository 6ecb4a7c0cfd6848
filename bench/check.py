"""How ``correct`` is decided: every job's whole output against the plain
reference of its keys, once the window has closed.

The comparison is exact, so its one number, the count of output positions
that differ from the reference, has the limit 0.  An output of the wrong
length counts every missing or extra position, and every position of the
common prefix that differs.
"""

from __future__ import annotations

import numpy as np

# The only number compared, with its limit: an exact sort leaves nothing.
LIMITS = {"mismatched_keys": 0}


def mismatched(out, ref: np.ndarray) -> int:
    """Positions of ``out`` that differ from ``ref``, the length difference
    included."""
    out = np.asarray(out).reshape(-1)
    m = min(out.size, ref.size)
    return int(np.count_nonzero(out[:m] != ref[:m])) + abs(out.size - ref.size)


def judge(outputs, key_index, key_sets, reference) -> dict:
    """Compare each job's output (``None`` for a job that raised) with the
    reference of the key set it sorted.  Returns the numbers compared, each
    beside its limit, and the count of jobs that failed."""
    refs = {}
    bad_keys = 0
    failed = 0
    for out, i in zip(outputs, key_index):
        if i not in refs:
            refs[i] = reference(key_sets[i])
        miss = refs[i].size if out is None else mismatched(out, refs[i])
        bad_keys += miss
        failed += miss > 0
    numbers = {"mismatched_keys": {"value": bad_keys,
                                   "limit": LIMITS["mismatched_keys"]},
               "jobs_checked": {"value": len(outputs), "limit": 1}}
    correct = (bad_keys <= LIMITS["mismatched_keys"]
               and len(outputs) >= 1)
    return {"correct": correct, "failed": failed, "numbers": numbers}
