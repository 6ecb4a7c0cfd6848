"""Test generator: a descending ramp of int32 keys, shifted by the seed."""

import numpy as np


def generate(params, seed, index):
    n = int(params["n"])
    return (np.arange(n, 0, -1, dtype=np.int64) + seed % 7 + index).astype(
        np.int32)
