# TPU Pallas kernels for the paper's compute hot-spots:
#   vq_assign        — multi-head nearest-codebook assignment (App. A.2 trick:
#                      one MXU matmul + row argmax + one-hot gather-matmul)
#   gated_attention  — streaming σ(QK^T)V (paper eq. 1). σ is element-wise, so
#                      KV tiles accumulate independently: no online-softmax
#                      running max / rescale pass — cheaper than flash-softmax
#                      on TPU (DESIGN.md §3).
# Each package: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
# wrapper), ref.py (pure-jnp oracle used by the test sweeps).
import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: everywhere except a TPU
    backend, where they always compile. There is no fallback — a backend
    that fails to start raises here instead of silently interpreting."""
    return jax.default_backend() != "tpu"
