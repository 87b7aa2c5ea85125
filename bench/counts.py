"""Operations and bytes from shapes: the model's training work and the similarity kernel's.

Nothing here is measured; every number is worked out from the cell's sizes.
"""
from __future__ import annotations


def mlp_param_count(dims) -> int:
    """Parameters of a dense MLP with biases, ``dims = (in, hidden..., out)``."""
    return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))


def train_flops_per_sample(n_params: int) -> int:
    """Forward (2P) and backward (4P) multiply-adds of one training sample."""
    return 6 * n_params


def round_train_flops(n_params: int, distinct_clients: int, n_local_steps: int,
                      batch_size: int) -> int:
    """Model FLOPs of one round's local work. Padded slots train with weight 0
    and are not counted."""
    return train_flops_per_sample(n_params) * distinct_clients * n_local_steps * batch_size


def similarity_flops(n: int, d: int) -> int:
    """The (n, n) Gram over an (n, d) store: 2 n^2 d."""
    return 2 * n * n * d


def similarity_bytes(n: int, d: int) -> int:
    """Least float32 traffic of the fused kernel: read the store once, write (n, n)."""
    return 4 * (n * d + n * n)


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bytes_per_s: float) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    compute, memory = flops / peak_flops, nbytes / peak_bytes_per_s
    return (compute, "compute") if compute >= memory else (memory, "memory")
