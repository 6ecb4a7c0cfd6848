"""Batched serving example: prefill + decode with KV caches (transformer)
and O(1) recurrent state (mamba2), via the production serve driver.

    PYTHONPATH=src python examples/serve_lm.py
"""

from repro.compile_cache import enable_compile_cache
from repro.launch.serve import main

enable_compile_cache()

print("=== transformer (qwen2-family, KV cache) ===")
main(["--arch", "qwen2-1.5b", "--smoke", "--requests", "8",
      "--prompt-len", "16", "--gen-len", "32"])

print("\n=== SSM (mamba2-family, O(1) state) ===")
main(["--arch", "mamba2-130m", "--smoke", "--requests", "8",
      "--prompt-len", "16", "--gen-len", "32"])
