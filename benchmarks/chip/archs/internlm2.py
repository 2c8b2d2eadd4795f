"""InternLM2's block, as the benchmark knows it: the Llama-style dense
decoder layer.

  x = x + wo(attend(rope(wq(n1(x))), rope(wk(n1(x))), wv(n1(x))))
  x = x + w_down(w_up(n2(x)) * silu(w_gate(n2(x))))

RMSNorm before each half (gains stored as offsets from 1), grouped-query
attention (query head i reads key/value head i // (hq / hk)), rotate-half
RoPE on the whole head, a SwiGLU MLP, no biases.  Every layer is the same
block, so every leaf is at every layer.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

import flops
from weights import Leaf

KINDS = ("attn", "mlp")


def dims(config: Dict[str, Any]) -> Dict[str, int]:
    h, hq = config["hidden_size"], config["num_attention_heads"]
    return dict(
        h=h,
        f=config["intermediate_size"],
        hq=hq,
        hk=config["num_key_value_heads"],
        dh=config.get("head_dim") or h // hq,
    )


def arch_config(config: Dict[str, Any]):
    """The program's ArchConfig for a configuration file."""
    from repro.models.lm import ArchConfig

    return ArchConfig(
        name=config["name"],
        family="dense",
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab=config["vocab_size"],
        block_pattern=(KINDS,),
        dtype=config["torch_dtype"],
        source=config["source"],
    )


def layer_leaves(config: Dict[str, Any], li: int) -> Dict[str, Leaf]:
    d = dims(config)
    h, f, hq, hk, dh = d["h"], d["f"], d["hq"], d["hk"], d["dh"]
    std = config["initializer_range"]
    return {
        "attn_norm": Leaf("attn", "ln", (h,)),
        "wq": Leaf("attn", "wq", (h, hq * dh), std),
        "wk": Leaf("attn", "wk", (h, hk * dh), std),
        "wv": Leaf("attn", "wv", (h, hk * dh), std),
        "wo": Leaf("attn", "wo", (hq * dh, h), std),
        "mlp_norm": Leaf("mlp", "ln", (h,)),
        "w_up": Leaf("mlp", "wu", (h, f), std),
        "w_gate": Leaf("mlp", "wg", (h, f), std),
        "w_down": Leaf("mlp", "wd", (f, h), std),
    }


def layer(ref, w, x, pos):
    """One layer of the reference, from ``Reference``'s primitives."""
    d = dims(ref.config)
    b, s, _ = x.shape
    xin = ref.rmsnorm(x, w["attn_norm"])
    q = ref.mm("bsh,hd->bsd", xin, w["wq"]).reshape(b, s, d["hq"], d["dh"])
    k = ref.mm("bsh,hd->bsd", xin, w["wk"]).reshape(b, s, d["hk"], d["dh"])
    v = ref.mm("bsh,hd->bsd", xin, w["wv"]).reshape(b, s, d["hk"], d["dh"])
    q, k = ref.rope(q, pos), ref.rope(k, pos)
    rep = d["hq"] // d["hk"]  # query head i reads key/value head i // rep
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    o = ref.attend(q, k, v).reshape(b, s, d["hq"] * d["dh"])
    x = x + ref.mm("bsd,dh->bsh", o, w["wo"])
    xin = ref.rmsnorm(x, w["mlp_norm"])
    up = ref.mm("bsh,hf->bsf", xin, w["w_up"])
    gate = jax.nn.silu(ref.mm("bsh,hf->bsf", xin, w["w_gate"]))
    return x + ref.mm("bsf,fh->bsh", up * gate, w["w_down"])


def matmul_params(config: Dict[str, Any]) -> int:
    """Weights every token multiplies: per layer q and o (h x hq*dh each), k
    and v (h x hk*dh each) and three h x f matrices; the head (h x V)."""
    d = dims(config)
    h, hq, hk, dh, f = d["h"], d["hq"], d["hk"], d["dh"], d["f"]
    per_layer = 2 * h * hq * dh + 2 * h * hk * dh + 3 * h * f
    return config["num_hidden_layers"] * per_layer + flops.head_params(config)


def attention_shapes(config: Dict[str, Any]) -> List[Tuple[int, int, int, int]]:
    """(query heads, key/value heads, qk head dim, v head dim) of each
    layer's causal attention."""
    d = dims(config)
    return [(d["hq"], d["hk"], d["dh"], d["dh"])] * config["num_hidden_layers"]


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    attn = sum(flops.causal_attention(seq_len, hq, qk, v) for hq, _, qk, v in attention_shapes(config))
    return float(flops.TRAIN_PER_PARAM * matmul_params(config) + attn)
