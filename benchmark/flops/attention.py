"""Operations and bytes of the flash attention kernels from their shapes.

Forward: two products of (T x D) by (D x T) shapes for every head, 4*B*H*T*T*D
operations, half of it under a causal mask. Backward (dq and dkv together):
five such products against the forward's two, 2.5 times the forward. Bytes:
q, k, v and o once each forward; those, do, dq, dk and dv once each backward.
"""


def flash_fwd(B, H, T, D, causal, dtype_bytes=2):
    ops = 4.0 * B * H * T * T * D * (0.5 if causal else 1.0)
    return ops, 4.0 * B * H * T * D * dtype_bytes


def flash_bwd(B, H, T, D, causal, dtype_bytes=2):
    ops, _ = flash_fwd(B, H, T, D, causal, dtype_bytes)
    return 2.5 * ops, 8.0 * B * H * T * D * dtype_bytes


def least_seconds(ops, nbytes, peaks):
    """The least time the chip could take, and which bound sets it."""
    t_ops, t_bytes = ops / peaks["flops_bf16"], nbytes / peaks["bytes_per_s"]
    return max(t_ops, t_bytes), ("ops" if t_ops >= t_bytes else "bytes")
