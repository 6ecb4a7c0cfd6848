"""End-to-end training driver: train a reduced Qwen2-family model for a few
hundred steps on CPU with checkpointing, then resume to show crash recovery.

    PYTHONPATH=src python examples/train_lm.py
"""

import tempfile

from repro.compile_cache import enable_compile_cache
from repro.launch.train import main

enable_compile_cache()

with tempfile.TemporaryDirectory() as d:
    print("=== training 200 steps ===")
    main([
        "--arch", "qwen2-1.5b", "--smoke",
        "--steps", "200", "--seq", "64", "--batch", "8",
        "--microbatches", "2",
        "--ckpt-dir", d, "--ckpt-every", "100",
    ])
    print("\n=== simulated restart: resumes from step 200 checkpoint ===")
    main([
        "--arch", "qwen2-1.5b", "--smoke",
        "--steps", "200", "--seq", "64", "--batch", "8",
        "--microbatches", "2",
        "--ckpt-dir", d,
    ])
