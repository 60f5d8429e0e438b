"""Model FLOPs per trained token of the ``ssm`` family (Mamba-2).

    flops/token = 6 · N_mm + 3 · L · (2·q·n + 2·h·q·p + 4·h·p·n)

``N_mm`` counts the parameters that multiply a token's activations:
per layer the input projection d · (2·d_inner + 2·n + h) (z, x, B, C
and dt) and the output projection d_inner · d, plus the output head
(d · V, the tied embedding).  A matmul parameter costs 2 FLOPs forward
and 4 backward.  The SSD adds, per token per layer forward, with q the
chunk, n the state, h the heads and p the head dim, counted over the
whole chunk square as the program computes it: C·Bᵀ within the chunk
2·q·n, its decayed product with Δ·x 2·h·q·p, the chunk's states from B
and Δ·x 2·h·p·n, and the states read out by C 2·h·p·n; times 3 with
the backward pass.  The depthwise conv, norms, gates, softplus and
exponentials are left out, as norms are in `flops.dense`.

mamba2-1.3b, 12 layers, seq 1024, chunk 256: N_mm per layer
= 2048 · (8192 + 256 + 64) + 4096 · 2048 = 25,821,184; N_mm
= 12 · 25,821,184 + 2048 · 50,280 = 412,827,648; 6 · N_mm = 2.477
GFLOP; SSD 3 · 12 · (65,536 + 2,097,152 + 2,097,152) = 3 · 12 ·
4,259,840 = 0.153 GFLOP; 2.630 GFLOP per token.
"""


def _widths(c: dict) -> tuple:
    di = c["ssm_expand"] * c["d_model"]
    n = c["ssm_groups"] * c["ssm_state"]
    return di, n, di // c["ssm_headdim"], c["ssm_headdim"]


def matmul_params(c: dict) -> int:
    d = c["d_model"]
    di, n, h, _ = _widths(c)
    per_layer = d * (2 * di + 2 * n + h) + di * d
    return c["num_layers"] * per_layer + d * c["vocab_size"]


def flops_per_token(c: dict, seq: int) -> float:
    di, n, h, p = _widths(c)
    q = c["ssm_chunk"]
    ssd = 2 * q * n + 2 * h * q * p + 4 * h * p * n
    return 6.0 * matmul_params(c) + 3 * c["num_layers"] * ssd
