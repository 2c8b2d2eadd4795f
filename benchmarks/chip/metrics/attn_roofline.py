"""Causal attention's share of its roofline, in %: the least time the chip
could take for the forward and backward of every layer's causal attention
at the cell's shapes (``peaks.roofline_s`` of ``work``), over the device
time under the ``attn`` scope, a step, mean over chips.  ``attn`` holds all
of attention's work whatever implements it (the kernels and the copies
around them, or the jnp path), so the share cannot pass 100%.  The
kernels' own time is the ``flash`` scope, which ``scoped.report`` logs."""

import jax.numpy as jnp

import flops
import peaks
import scoped

SCOPES = ("flash",)


def work(run):
    """(FLOPs, HBM bytes) of causal attention's forward and backward a step,
    mean over the chips: the architecture's ``attention_shapes`` at the
    traffic's sequence length, over the step's tokens.  Nothing recomputed
    is counted."""
    seq = run.traffic["seq_len"]
    itemsize = jnp.dtype(run.config["torch_dtype"]).itemsize
    shapes = run.arch.attention_shapes(run.config)
    per_token = sum(flops.causal_attention(seq, hq, qk, v) for hq, _, qk, v in shapes)
    bytes_per_token = sum(flops.causal_attention_bytes(hq, hk, qk, v, itemsize) for hq, hk, qk, v in shapes)
    tokens = run.tokens_per_step / run.chips
    return per_token * tokens, bytes_per_token * tokens


def read(run):
    def share(s):
        ms = scoped.scope_ms(s, "attn")
        return None if ms is None else 100.0 * 1e3 * peaks.roofline_s(run.peak, *work(run)) / ms

    return scoped.reading(run, share)
