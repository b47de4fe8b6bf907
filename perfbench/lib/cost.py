"""Operations and bytes of the work the benchmark counts, from shapes.

These are the algorithm's numbers, independent of how the program
implements them: a kernel that reads its weights twice still counts them
once here, so a share of the roofline shows the waste. What one layer
holds comes from the configuration's architecture module.
"""
from __future__ import annotations

from .model_config import ModelConfig, architecture


def layer_matmuls(cfg: ModelConfig) -> list[tuple[str, int, int]]:
    """(site, K, N) of the fused OVP matmul calls of one layer."""
    return architecture(cfg).layer_matmuls(cfg)


def ovp_matmul(m: int, k: int, n: int, act_bytes: int = 2,
               out_bytes: int = 4) -> tuple[float, float]:
    """(operations, bytes) of one fused W4A4 OVP matmul of M rows: 4-bit
    packed weights (K*N/2 bytes) with a float32 scale per output column,
    activations in at `act_bytes` with a float32 scale per row, the
    product out at `out_bytes`."""
    ops = 2.0 * m * k * n
    nbytes = k * n / 2 + 4 * n + m * k * act_bytes + 4 * m + m * n * out_bytes
    return ops, nbytes


def step_ovp_matmuls(cfg: ModelConfig, rows: int) -> list[tuple[int, int, int]]:
    """(M, K, N) of every fused matmul call of one forward of `rows`
    rows through all layers."""
    return [(rows, k, n) for _ in range(cfg.num_hidden_layers)
            for _, k, n in layer_matmuls(cfg)]


def least_time_s(ops: float, nbytes: float, ops_per_s: float,
                 bytes_per_s: float) -> float:
    return max(ops / ops_per_s, nbytes / bytes_per_s)


def flops_per_token(cfg: ModelConfig, context: float) -> float:
    """Model operations to generate one token at `context` positions of
    history."""
    return architecture(cfg).flops_per_token(cfg, context)
