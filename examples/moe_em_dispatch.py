"""MoE expert dispatch as external-memory Alltoallv.

Experts are the thesis' virtual processors: tokens are bucketised by
destination expert under a capacity bound ω (thesis §6.4) and delivered
directly into per-expert buffers.  The hierarchical grouping (one group per
data-parallel shard) is the thesis' real/virtual processor split — under
pjit the group dim stays sharded and the dispatch lowers to the same
all-to-all EM-Alltoallv-Par performs.

    PYTHONPATH=src python examples/moe_em_dispatch.py
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models.blocks import moe_apply, moe_apply_dense_oracle, moe_params

enable_compile_cache()
cfg = get_config("kimi-k2-1t-a32b").smoke()
print(f"MoE: {cfg.n_experts} experts, top-{cfg.top_k}, "
      f"capacity_factor={cfg.capacity_factor}")

params = moe_params(jax.random.PRNGKey(0), cfg)
x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 32, cfg.d_model)),
                jnp.float32)

# EM dispatch (grouped, capacity-bounded) vs the dense all-experts oracle.
for groups in (1, 2, 4):
    y, aux = moe_apply(cfg, params, x, n_groups=groups)
    oracle = moe_apply_dense_oracle(cfg, params, x)
    err = float(jnp.abs(y - oracle).max())
    print(f"groups={groups}: max |EM - oracle| = {err:.2e}  (aux={float(aux):.3f})")

# Capacity pressure → token dropping, like exceeding the thesis' ω bound.
tight = dataclasses.replace(cfg, capacity_factor=0.25)
y_t, _ = moe_apply(tight, params, x, n_groups=2)
print(f"capacity_factor=0.25 drops tokens: output moved by "
      f"{float(jnp.abs(y_t - oracle).max()):.3f} (finite: "
      f"{bool(jnp.isfinite(y_t).all())})")
