"""Hand-written Pallas TPU kernels for the hot ops (the role the reference's
CUDA kernels play: flash attention phi/kernels/gpu/flash_attn_kernel.cu,
paged decode attention fused_multi_transformer_op.cu, weight-only GEMM
funcs/weight_only_gemv.cu).  On a TPU they are compiled by Mosaic; the CPU
tests run the same kernels through the Pallas interpreter."""
import jax


def interpret() -> bool:
    """Whether kernels launched without an explicit ``interpret=`` run in
    the Pallas interpreter: only when the backend IS ``cpu``.  Every other
    backend compiles the kernel, and a kernel that cannot be compiled
    there is an error — never a reason to interpret, and never a reason
    to take another path."""
    return jax.default_backend() == "cpu"
