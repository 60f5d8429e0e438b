"""Model FLOPs per trained token of the ``dense`` family.

    flops/token = 6 · N_mm + 12 · L · S · H · hd

``N_mm`` counts the parameters that multiply a token's activations:
per layer the four attention projections (d · H·hd for q and the
output, d · Hk·hd for k and v) and the MLP (2 or 3 · d · d_ff), plus
the output head (d · V, the tied embedding; the embedding lookup is no
matmul).  A matmul parameter costs 2 FLOPs forward and 4 backward.
Attention adds QKᵀ and PV, 2 · 2 · S · H·hd per token per layer
forward, times 3 with the backward pass, counted over the whole causal
square as the kernels compute it (the PaLM paper's convention).
Norm gains, softmax and activations are left out.

gpt2-xl-paper, 12 layers, seq 1024: N_mm = 12 · 30,720,000 + 80,411,200
= 449,051,200; 6 · N_mm = 2.694 GFLOP; attention 12 · 12 · 1024 · 1600
= 0.236 GFLOP; 2.930 GFLOP per token.
"""


def matmul_params(c: dict) -> int:
    d, hd = c["d_model"], c["head_dim"]
    attn = d * hd * (2 * c["num_heads"] + 2 * c["num_kv_heads"])
    mlp = (3 if c.get("mlp_gated") else 2) * d * c["d_ff"]
    return c["num_layers"] * (attn + mlp) + d * c["vocab_size"]


def flops_per_token(c: dict, seq: int) -> float:
    attn = 12 * c["num_layers"] * seq * c["num_heads"] * c["head_dim"]
    return 6.0 * matmul_params(c) + attn
