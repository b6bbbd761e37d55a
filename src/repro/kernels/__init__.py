"""Pallas TPU kernels for the paper's compute hot-spots.

Layout per kernel: <name>.py (pl.pallas_call + BlockSpec tiling),
ops.py (jit'd public wrappers), ref.py (pure-jnp oracles).

  user_scores — fused U·Qᵀ matmul + rank-table bucketize (§4.3 step 1,
                the O(nd) query hot loop; memory-bound, lookup rides free)
  table_build — fused U·Samplesᵀ + stratified weighted histogram (Eq. 1,
                Algorithm 1's per-user hot loop)
  exact_rank  — streaming Definition-1 counts (refinement / oracle;
                compute-bound item streaming)

Kernels compile natively on a TPU and run interpreted on the CPU; the
choice follows the backend (`interpret_mode`).
"""
import jax


def interpret_mode() -> bool:
    """True iff JAX's default backend is the CPU, where Pallas kernels can
    only run interpreted. Every pallas_call reads it when it is traced, so
    it follows the platform the process runs on; nothing overrides it, so
    on a TPU every kernel runs compiled."""
    return jax.default_backend() == "cpu"
