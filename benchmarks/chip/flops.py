"""Model FLOPs of one training token: what the forward and backward passes
require, with nothing recomputed counted twice; and the bytes that a
kernel's inputs and outputs must move.  An architecture's module
(``archs/<architecture>.py``) counts its own layers with these, its
``train_flops_per_token`` is what ``mfu`` divides by, and an architecture
that routes counts only the weights a token is routed through.

Each weight a token multiplies costs 2 FLOPs forward and 4 backward (input
and weight gradients): ``TRAIN_PER_PARAM`` = 6.  The embedding is a lookup
and costs none; the LM head is h x V.
"""

from typing import Any, Dict

TRAIN_PER_PARAM = 6


def head_params(config: Dict[str, Any]) -> int:
    return config["hidden_size"] * config["vocab_size"]


def causal_attention(seq_len: int, heads: int, qk_dim: int, v_dim: int) -> int:
    """Training FLOPs of causal attention per token and layer: forward half
    of QK^T (s x qk_dim a head) and of PV (s x v_dim a head), backward twice
    the forward."""
    return 3 * seq_len * heads * (qk_dim + v_dim)


def causal_attention_bytes(heads: int, kv_heads: int, qk_dim: int, v_dim: int, itemsize: int) -> int:
    """HBM bytes per token and layer that any implementation of causal
    attention's forward and backward must move: the forward reads q, k, v
    and writes o and the row log-sum-exp (f32); the backward reads q, k, v,
    o, dO and the log-sum-exp and writes dq, dk, dv.  ``itemsize`` is the
    bytes of one element of the configuration's dtype."""
    q, k, v, o = heads * qk_dim, kv_heads * qk_dim, kv_heads * v_dim, heads * v_dim
    lse = 4 * heads
    forward = itemsize * (q + k + v + o) + lse
    backward = itemsize * (q + k + v + 2 * o + q + k + v) + lse
    return forward + backward
