"""Model FLOPs of one training token: what the forward and backward passes
require, with nothing recomputed counted twice.

Dense arithmetic as in ``benchmarks/roofline.py``'s ``active_params``:
per layer, attention q and o (h x hq*dh each), k and v (h x hk*dh each) and a
SwiGLU MLP of three h x f matrices; plus the LM head (h x V).  The embedding
is a lookup and costs no FLOPs.  Each weight costs 2 FLOPs per token forward
and 4 backward (input and weight gradients): 6 N.  Causal attention adds, per
layer and token, 2 s hq dh forward (half of QK^T and of PV) and twice that
backward: 6 s hq dh.
"""

from typing import Any, Dict


def matmul_params(config: Dict[str, Any]) -> int:
    h = config["hidden_size"]
    hq = config["num_attention_heads"]
    hk = config["num_key_value_heads"]
    dh = config.get("head_dim") or h // hq
    f = config["intermediate_size"]
    per_layer = 2 * h * hq * dh + 2 * h * hk * dh + 3 * h * f
    return config["num_hidden_layers"] * per_layer + h * config["vocab_size"]


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    h = config["hidden_size"]
    hq = config["num_attention_heads"]
    dh = config.get("head_dim") or h // hq
    attn = 6 * config["num_hidden_layers"] * seq_len * hq * dh
    return float(6 * matmul_params(config) + attn)
