"""Layer library: pure-jnp forwards + initializers for every assigned family.

All functions are plain ``f(params, x, ...) -> y`` JAX code; the F/B/W split
is obtained by wrapping whole pipeline chunks with ``auto_fbw`` (core.passes),
so nothing here needs a hand-written backward.

Tensor parallelism follows Megatron: column-parallel in-projections,
row-parallel out-projections with a ``psum`` over the TP axis.  Parameters
are initialized at *global* shapes; shard_map + the name-based rules in
launch/sharding_rules.py hand each rank its local shard.  Divisibility
decisions live here (``cfg["tp_size"]``):

  * q heads % tp != 0  -> attention fully replicated (params named *_rep,
    no out-psum); the MLP of the same block stays TP.  (gemma2 8H, whisper 6H)
  * kv heads % tp != 0 (but q ok) -> kv projections replicated; each rank
    dynamically selects the kv heads its local q heads map to.
  * MoE experts are padded to a multiple of tp; padded experts are masked
    out of the router.
  * recurrent kinds (sLSTM/mLSTM/RG-LRU) keep replicated weights (their
    states are elementwise; TP would buy little and cost collectives).

Families covered: dense GQA transformer (RoPE, local windows, logit
soft-capping), MLA (DeepSeek-V3), MoE (shared + routed top-k), xLSTM
(sLSTM + chunkwise mLSTM), RG-LRU (RecurrentGemma), encoder-decoder layers
(Whisper; concat-carry), and a vocab-parallel cross-entropy sink.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import attention as flash

PyTree = Any

__all__ = [
    "ShardCtx",
    "init_layer",
    "apply_layer",
    "apply_block",
    "LAYER_KINDS",
    "rmsnorm",
    "rope",
    "attention",
    "vocab_parallel_ce",
    "pad_to_multiple",
]


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Tensor-parallel context threaded through all layers."""

    tp_axis: Optional[str] = None  # mesh axis name, None = no TP
    tp_size: int = 1
    platform: Optional[str] = None  # the step's devices; None = default backend

    def psum(self, x):
        if self.tp_axis is None:
            return x
        return jax.lax.psum(x, self.tp_axis)

    def pmax(self, x):
        """Gradient-free pmax (used for softmax max-shift, which cancels
        analytically; jax has no differentiation rule for pmax)."""
        if self.tp_axis is None:
            return jax.lax.stop_gradient(x)
        axis = self.tp_axis

        @jax.custom_vjp
        def f(v):
            return jax.lax.pmax(v, axis)

        f.defvjp(lambda v: (f(v), None), lambda _, g: (jnp.zeros_like(g),))
        return f(x)

    def index(self):
        if self.tp_axis is None:
            return jnp.zeros((), jnp.int32)
        return jax.lax.axis_index(self.tp_axis)


def pad_to_multiple(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k


def _tp(cfg) -> int:
    return int(cfg.get("tp_size", 1) or 1)


def _q_sharded(cfg) -> bool:
    return cfg["n_heads"] % _tp(cfg) == 0


def _kv_sharded(cfg) -> bool:
    return _q_sharded(cfg) and cfg["n_kv_heads"] % _tp(cfg) == 0


# --------------------------------------------------------------------- #
# primitives
# --------------------------------------------------------------------- #
def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def rmsnorm(g, x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + g.astype(jnp.float32))).astype(
        x.dtype
    )


def rope(x, positions, theta=10000.0):
    """x: (b, s, h, d); positions: (s,) or (b, s)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    if positions.ndim == 1:
        ang = positions[None, :, None].astype(jnp.float32) * freqs
        ang = ang[:, :, None, :]  # (1, s, 1, half)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return out.astype(x.dtype)


def _softcap(x, cap):
    if cap is None or cap <= 0:
        return x
    return cap * jnp.tanh(x / cap)


# --------------------------------------------------------------------- #
# attention: causal self-attention on the TPU through the blocked kernel
# (kernels/attention.py) where it takes the input; otherwise dense for short
# sequences, q-block-chunked + remat for long sequences so activation memory
# stays O(s * d) per layer
# --------------------------------------------------------------------- #
def _attend_dense(q, k, v, causal, window, softcap, q_offset=0):
    """q: (b, sq, hq, d); k/v: (b, sk, hq, d) head-matched -> (b, sq, hq, d)."""
    sq = q.shape[1]
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(d)
    logits = _softcap(logits, softcap)
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask = mask & (kpos[None, :] <= qpos[:, None])
    if window is not None and window > 0:
        mask = mask & (kpos[None, :] > qpos[:, None] - window)
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _attend_chunked(q, k, v, causal, window, softcap, block=1024):
    """Scan over query blocks, remat inside: O(s*d) saved residuals."""
    b, s, hq, d = q.shape
    dv = v.shape[-1]  # may differ from d (MLA: qk 192, v 128)
    nb = -(-s // block)
    pad = nb * block - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = q.reshape(b, nb, block, hq, d).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def one_block(args):
        qi, i = args
        return _attend_dense(
            qi, k, v, causal, window, softcap, q_offset=i * block
        )

    def body(_, args):
        return None, one_block(args)

    _, out = jax.lax.scan(body, None, (qb, jnp.arange(nb)))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, nb * block, hq, dv)
    return out[:, :s]


def _repeat_kv_heads(q_heads, k, v):
    """Repeat grouped kv heads so k/v carry one head per q head."""
    rep = q_heads // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def _select_kv_heads(q_heads_local, k, v, cfg, ctx: ShardCtx):
    """q sharded, kv replicated: local q head i -> global q head
    r*hq_l + i -> kv head (r*hq_l + i) // group, one kv head per local q head."""
    group = cfg["n_heads"] // cfg["n_kv_heads"]
    gq = ctx.index() * q_heads_local + jnp.arange(q_heads_local)
    sel = gq // group
    return jnp.take(k, sel, axis=2), jnp.take(v, sel, axis=2)


def _match_kv_heads(q_heads_local, k, v, cfg, ctx: ShardCtx):
    """Expand/select kv heads so k/v carry one head per local q head."""
    if _kv_sharded(cfg) or _tp(cfg) == 1:
        return _repeat_kv_heads(q_heads_local, k, v)
    return _select_kv_heads(q_heads_local, k, v, cfg, ctx)


@jax.named_scope("attn")
def attention(q, k, v, *, causal=True, window=None, softcap=None, block=1024,
              platform=None):
    """q: (b, sq, hq, d); k/v: (b, sk, hk, d), q head i reading kv head
    i // (hq // hk).  ``platform`` is that of the step's devices (None: the
    process's default backend); where the blocked kernel takes the input
    there, it runs, else the jnp path below."""
    kb = flash.kernel_block(
        q.shape, k.shape, v.shape, causal=causal, window=window,
        softcap=softcap, platform=platform or jax.default_backend(),
    )
    if kb is not None:
        return flash.causal_attention(q, k, v, kb)
    k, v = _repeat_kv_heads(q.shape[2], k, v)
    if q.shape[1] <= 2 * block:
        return _attend_dense(q, k, v, causal, window, softcap)
    return _attend_chunked(q, k, v, causal, window, softcap, block)


# --------------------------------------------------------------------- #
# dense attention + MLP
# --------------------------------------------------------------------- #
def init_attn(key, cfg, dtype):
    h, hq, hk = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    dh = cfg.get("head_dim") or h // hq
    qs, kvs = _q_sharded(cfg), _kv_sharded(cfg)
    ks = jax.random.split(key, 5)
    sc = 1.0 / math.sqrt(h)
    so = sc / math.sqrt(2 * cfg["n_layers"])
    return {
        "ln": jnp.zeros((h,), dtype),
        ("wq" if qs else "wq_rep"): _normal(ks[0], (h, hq * dh), sc, dtype),
        ("wk" if kvs else "wk_rep"): _normal(ks[1], (h, hk * dh), sc, dtype),
        ("wv" if kvs else "wv_rep"): _normal(ks[2], (h, hk * dh), sc, dtype),
        ("wo" if qs else "wo_rep"): _normal(ks[3], (hq * dh, h), so, dtype),
    }


def apply_attn(p, x, positions, cfg, ctx: ShardCtx, *, window=None):
    b, s, _ = x.shape
    tp = _tp(cfg)
    qs = _q_sharded(cfg)
    hq_l = cfg["n_heads"] // tp if qs else cfg["n_heads"]
    hk_l = cfg["n_kv_heads"] // tp if _kv_sharded(cfg) else cfg["n_kv_heads"]
    dh = cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]
    xin = rmsnorm(p["ln"], x)
    wq = p.get("wq", p.get("wq_rep"))
    wk = p.get("wk", p.get("wk_rep"))
    wv = p.get("wv", p.get("wv_rep"))
    wo = p.get("wo", p.get("wo_rep"))
    q = (xin @ wq).reshape(b, s, hq_l, dh)
    k = (xin @ wk).reshape(b, s, hk_l, dh)
    v = (xin @ wv).reshape(b, s, hk_l, dh)
    q, k = rope(q, positions), rope(k, positions)
    if not (_kv_sharded(cfg) or tp == 1):
        k, v = _select_kv_heads(hq_l, k, v, cfg, ctx)
    o = attention(
        q, k, v, causal=True, window=window, softcap=cfg.get("attn_softcap"),
        platform=ctx.platform,
    )
    o = o.reshape(b, s, hq_l * dh) @ wo
    return x + (ctx.psum(o) if qs and tp > 1 else o)


def init_mlp(key, cfg, dtype):
    h, f = cfg["d_model"], cfg["d_ff"]
    assert f % _tp(cfg) == 0, f"d_ff={f} not divisible by tp={_tp(cfg)}"
    ks = jax.random.split(key, 3)
    sc = 1.0 / math.sqrt(h)
    return {
        "ln": jnp.zeros((h,), dtype),
        "wu": _normal(ks[0], (h, f), sc, dtype),
        "wg": _normal(ks[1], (h, f), sc, dtype),
        "wd": _normal(ks[2], (f, h), sc / math.sqrt(2 * cfg["n_layers"]), dtype),
    }


def apply_mlp(p, x, cfg, ctx: ShardCtx):
    xin = rmsnorm(p["ln"], x)
    up = xin @ p["wu"]
    gate = jax.nn.silu(xin @ p["wg"])
    out = (up * gate) @ p["wd"]
    return x + (ctx.psum(out) if _tp(cfg) > 1 else out)


# -- MLA (DeepSeek-V3): latent-compressed attention ---------------------- #
def init_mla(key, cfg, dtype):
    h = cfg["d_model"]
    hq = cfg["n_heads"]
    assert _q_sharded(cfg), "MLA requires n_heads % tp == 0"
    dh = cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]
    d_q = cfg.get("q_lora_rank") or 1536
    d_kv = cfg.get("kv_lora_rank") or 512
    d_rope = cfg.get("qk_rope_head_dim") or 64
    ks = jax.random.split(key, 8)
    sc = 1.0 / math.sqrt(h)
    return {
        "ln": jnp.zeros((h,), dtype),
        "wdq": _normal(ks[0], (h, d_q), sc, dtype),
        "wuq": _normal(ks[1], (d_q, hq * (dh + d_rope)), 1 / math.sqrt(d_q), dtype),
        "wdkv": _normal(ks[2], (h, d_kv + d_rope), sc, dtype),
        "wuk": _normal(ks[3], (d_kv, hq * dh), 1 / math.sqrt(d_kv), dtype),
        "wuv": _normal(ks[4], (d_kv, hq * dh), 1 / math.sqrt(d_kv), dtype),
        "wo": _normal(ks[5], (hq * dh, h), sc / math.sqrt(2 * cfg["n_layers"]), dtype),
    }


def apply_mla(p, x, positions, cfg, ctx: ShardCtx):
    b, s, _ = x.shape
    tp = _tp(cfg)
    hq = cfg["n_heads"] // tp
    dh = cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]
    d_rope = cfg.get("qk_rope_head_dim") or 64
    d_kv = cfg.get("kv_lora_rank") or 512
    xin = rmsnorm(p["ln"], x)
    q_all = (xin @ p["wdq"]) @ p["wuq"]
    q_all = q_all.reshape(b, s, hq, dh + d_rope)
    q_nope, q_rope = q_all[..., :dh], q_all[..., dh:]
    ckv = xin @ p["wdkv"]  # (b, s, d_kv + d_rope); latent replicated over tp
    c, k_rope = ckv[..., :d_kv], ckv[..., d_kv:]
    k_nope = (c @ p["wuk"]).reshape(b, s, hq, dh)
    v = (c @ p["wuv"]).reshape(b, s, hq, dh)
    q_rope = rope(q_rope, positions)
    k_rope = rope(k_rope[:, :, None, :], positions)  # shared across heads
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, hq, d_rope))], axis=-1
    )
    o = attention(q, k, v, causal=True, platform=ctx.platform)
    o = o.reshape(b, s, hq * dh) @ p["wo"]
    return x + (ctx.psum(o) if tp > 1 else o)


# -- MoE: shared + routed top-k, experts sharded over the TP axis --------- #
def _e_pad(cfg) -> int:
    return pad_to_multiple(cfg["n_experts"], _tp(cfg))


def init_moe(key, cfg, dtype):
    h = cfg["d_model"]
    f = cfg["moe_d_ff"]
    e_p = _e_pad(cfg)
    n_sh = cfg.get("n_shared_experts", 0)
    ks = jax.random.split(key, 8)
    sc = 1.0 / math.sqrt(h)
    so = sc / math.sqrt(2 * cfg["n_layers"])
    params = {
        "ln": jnp.zeros((h,), dtype),
        "router": _normal(ks[0], (h, cfg["n_experts"]), sc, jnp.float32),
        "wu": _normal(ks[1], (e_p, h, f), sc, dtype),
        "wg": _normal(ks[2], (e_p, h, f), sc, dtype),
        "wd": _normal(ks[3], (e_p, f, h), so, dtype),
    }
    if n_sh:
        f_sh = f * n_sh
        assert f_sh % _tp(cfg) == 0
        params.update(
            {
                "swu": _normal(ks[4], (h, f_sh), sc, dtype),
                "swg": _normal(ks[5], (h, f_sh), sc, dtype),
                "swd": _normal(ks[6], (f_sh, h), so, dtype),
            }
        )
    return params


def _moe_route(p, tok, cfg):
    """Top-k routing with per-expert capacity positions (shared by both
    dispatch backends).  Returns (top_g, top_i, pos_nk, keep) all (N, k)."""
    e_p = _e_pad(cfg)
    k_top = cfg["topk"]
    logits = tok.astype(jnp.float32) @ p["router"]  # (N, E) real experts
    gates = jax.nn.softmax(logits, axis=-1)
    top_g, top_i = jax.lax.top_k(gates, k_top)  # (N, k)
    top_g = top_g / (jnp.sum(top_g, axis=-1, keepdims=True) + 1e-9)
    onehot = jax.nn.one_hot(top_i, e_p, dtype=jnp.float32)  # (N, k, E_p)
    # globally consistent per-expert slot positions: count assignments in
    # (n, k) order over the flattened stream so no two selections collide.
    n = onehot.shape[0]
    flat = onehot.reshape(n * k_top, e_p)
    pos_flat = jnp.cumsum(flat, axis=0) - flat
    pos_nk = jnp.sum(pos_flat * flat, axis=-1).reshape(n, k_top)
    return top_g, top_i, pos_nk, onehot


def _dispatch_einsum(tok, top_g, top_i, pos_nk, onehot, cap, e_l, ei, dtype):
    """Mesh-TF dense dispatch; O(N*k*cap) one-hot + O(N*E_l*cap*h) einsums.
    Reference implementation (exact, differentiable end-to-end)."""
    keep = pos_nk < cap
    pos_oh = jax.nn.one_hot(
        jnp.where(keep, pos_nk, cap).astype(jnp.int32), cap, dtype=jnp.float32
    )  # (N, k, cap) -- the expert axis is NOT materialized
    sel = (onehot * keep[..., None].astype(jnp.float32))  # (N, k, E_p)
    sel_l = jax.lax.dynamic_slice_in_dim(sel, ei, e_l, axis=2)
    disp_l = jnp.einsum("nke,nkc->nec", sel_l, pos_oh)
    comb_l = jnp.einsum("nke,nkc->nec", sel_l * top_g[..., None], pos_oh)
    xe = jnp.einsum("nec,nh->ech", disp_l, tok.astype(jnp.float32)).astype(dtype)
    return xe, comb_l


def apply_moe(p, x, cfg, ctx: ShardCtx):
    """Shared + routed top-k experts, capacity-bounded, EP over the TP axis.

    dispatch="scatter" (default): slot indices are scattered once
    (O(N*k)) and tokens are moved with gather/scatter-add -- no
    O(N*E*cap) dense tensors.  dispatch="einsum" keeps the Mesh-TF dense
    formulation as the differentiation-friendly oracle (tests assert both
    agree).  Router gradients flow through the combine weights either way.
    """
    b, s, h = x.shape
    tp = _tp(cfg)
    e = cfg["n_experts"]
    e_p = _e_pad(cfg)
    k_top = cfg["topk"]
    e_l = e_p // tp
    cap = cfg.get("capacity", None)
    if cap is None:
        cap = int(math.ceil(b * s * k_top / e * cfg.get("capacity_factor", 1.25)))
        cap = max(4, min(cap, b * s))
    xin = rmsnorm(p["ln"], x)
    tok = xin.reshape(b * s, h)
    n = tok.shape[0]
    top_g, top_i, pos_nk, onehot = _moe_route(p, tok, cfg)
    ei = ctx.index() * e_l

    if cfg.get("moe_dispatch", "scatter") == "einsum":
        xe, comb_l = _dispatch_einsum(
            tok, top_g, top_i, pos_nk, onehot, cap, e_l, ei, x.dtype
        )
        up = jnp.einsum("ech,ehf->ecf", xe, p["wu"])
        gate = jax.nn.silu(jnp.einsum("ech,ehf->ecf", xe, p["wg"]))
        out_e = jnp.einsum("ecf,efh->ech", up * gate, p["wd"])
        y = jnp.einsum("nec,ech->nh", comb_l, out_e.astype(jnp.float32))
    else:
        # scatter dispatch: flat slot = (expert - ei) * cap + pos
        loc_e = top_i - ei  # (N, k) local expert index (may be out of range)
        keep = (pos_nk < cap) & (loc_e >= 0) & (loc_e < e_l)
        flat = jnp.where(
            keep, loc_e * cap + pos_nk.astype(jnp.int32), e_l * cap
        ).astype(jnp.int32)  # sentinel row e_l*cap
        # inverse map: slot -> token row (sentinel n = zero row)
        inv = jnp.full((e_l * cap + 1,), n, jnp.int32)
        inv = inv.at[flat.reshape(-1)].set(
            jnp.broadcast_to(jnp.arange(n)[:, None], flat.shape).reshape(-1),
            mode="drop",
        )
        tok_pad = jnp.concatenate([tok, jnp.zeros((1, h), tok.dtype)], axis=0)
        xe = tok_pad[inv[:-1]].reshape(e_l, cap, h)
        up = jnp.einsum("ech,ehf->ecf", xe, p["wu"])
        gate = jax.nn.silu(jnp.einsum("ech,ehf->ecf", xe, p["wg"]))
        out_e = jnp.einsum("ecf,efh->ech", up * gate, p["wd"])
        # combine: gather each (n, k) selection's output and weight it
        out_flat = jnp.concatenate(
            [out_e.reshape(e_l * cap, h), jnp.zeros((1, h), out_e.dtype)], axis=0
        )
        picked = out_flat[flat]  # (N, k, h); sentinel row contributes zeros
        w = (top_g * keep.astype(jnp.float32)).astype(jnp.float32)
        y = jnp.einsum("nkh,nk->nh", picked.astype(jnp.float32), w)

    y = (ctx.psum(y) if tp > 1 else y).astype(x.dtype)
    if "swu" in p:
        up = tok @ p["swu"]
        gate = jax.nn.silu(tok @ p["swg"])
        sh = (up * gate) @ p["swd"]
        y = y + (ctx.psum(sh) if tp > 1 else sh)
    return x + y.reshape(b, s, h)


# -- xLSTM blocks (replicated weights; recurrent state is elementwise) ---- #
def init_slstm(key, cfg, dtype):
    h = cfg["d_model"]
    ks = jax.random.split(key, 5)
    sc = 1.0 / math.sqrt(h)
    return {
        "ln": jnp.zeros((h,), dtype),
        "si": _normal(ks[0], (h, h), sc, dtype),
        "sf": _normal(ks[1], (h, h), sc, dtype),
        "sz": _normal(ks[2], (h, h), sc, dtype),
        "sog": _normal(ks[3], (h, h), sc, dtype),
        "so": _normal(ks[4], (h, h), sc / math.sqrt(2 * cfg["n_layers"]), dtype),
    }


def apply_slstm(p, x, cfg, ctx: ShardCtx):
    """sLSTM: scalar-memory recurrence with exponential gating (stabilized)."""
    b, s, h = x.shape
    xin = rmsnorm(p["ln"], x)
    i_pre = (xin @ p["si"]).astype(jnp.float32)
    f_pre = (xin @ p["sf"]).astype(jnp.float32)
    z = jnp.tanh(xin @ p["sz"]).astype(jnp.float32)
    o = jax.nn.sigmoid(xin @ p["sog"]).astype(jnp.float32)

    def step(carry, t):
        c, n, m_ = carry
        i_t, f_t, z_t = i_pre[:, t], f_pre[:, t], z[:, t]
        m_new = jnp.maximum(f_t + m_, i_t)
        i_e = jnp.exp(i_t - m_new)
        f_e = jnp.exp(f_t + m_ - m_new)
        c = f_e * c + i_e * z_t
        n = f_e * n + i_e
        return (c, n, m_new), c / jnp.maximum(n, 1.0)

    init = (
        jnp.zeros((b, h), jnp.float32),
        jnp.zeros((b, h), jnp.float32),
        jnp.full((b, h), -1e30, jnp.float32),
    )
    _, hs = jax.lax.scan(step, init, jnp.arange(s))
    hs = hs.transpose(1, 0, 2).astype(x.dtype)  # (b, s, h)
    return x + ((o.astype(x.dtype) * hs) @ p["so"])


def init_mlstm(key, cfg, dtype):
    h = cfg["d_model"]
    nh = cfg["n_heads"]
    ks = jax.random.split(key, 6)
    sc = 1.0 / math.sqrt(h)
    return {
        "ln": jnp.zeros((h,), dtype),
        "mq": _normal(ks[0], (h, h), sc, dtype),
        "mk": _normal(ks[1], (h, h), sc, dtype),
        "mv": _normal(ks[2], (h, h), sc, dtype),
        "mfg": _normal(ks[3], (h, nh), sc, dtype),
        "mig": _normal(ks[4], (h, nh), sc, dtype),
        "mo": _normal(ks[5], (h, h), sc / math.sqrt(2 * cfg["n_layers"]), dtype),
    }


def apply_mlstm(p, x, cfg, ctx: ShardCtx, chunk=128):
    """mLSTM matrix memory in chunkwise-parallel (linear-attention) form."""
    b, s, h = x.shape
    nh = cfg["n_heads"]
    dh = h // nh
    xin = rmsnorm(p["ln"], x)
    q = (xin @ p["mq"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
    k = (xin @ p["mk"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3) / math.sqrt(dh)
    v = (xin @ p["mv"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
    f_g = jax.nn.sigmoid((xin @ p["mfg"]).astype(jnp.float32)).transpose(0, 2, 1)
    i_g = jax.nn.sigmoid((xin @ p["mig"]).astype(jnp.float32)).transpose(0, 2, 1)

    nc = -(-s // chunk)
    pad = nc * chunk - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        f_g = jnp.pad(f_g, ((0, 0), (0, 0), (0, pad)), constant_values=1.0)
        i_g = jnp.pad(i_g, ((0, 0), (0, 0), (0, pad)))
    sh = (b, nh, nc, chunk)
    qc = q.reshape(b, nh, nc, chunk, dh).transpose(2, 0, 1, 3, 4)
    kc = k.reshape(b, nh, nc, chunk, dh).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, nh, nc, chunk, dh).transpose(2, 0, 1, 3, 4)
    fc = f_g.reshape(*sh).transpose(2, 0, 1, 3)
    ic = i_g.reshape(*sh).transpose(2, 0, 1, 3)

    @jax.checkpoint
    def chunk_step(C, args):
        qi, ki, vi, fi, ii = args
        logf = jnp.log(fi + 1e-6)  # (b, nh, c)
        cum = jnp.cumsum(logf, axis=-1)
        total = cum[..., -1:]
        decay = jnp.exp(cum[..., :, None] - cum[..., None, :])
        causal = jnp.tril(jnp.ones((qi.shape[-2], qi.shape[-2]), bool))
        att = jnp.einsum("bhqd,bhkd->bhqk", qi, ki).astype(jnp.float32)
        att = att * jnp.where(causal[None, None], decay, 0.0)
        att = att * ii[..., None, :]
        intra = jnp.einsum("bhqk,bhkd->bhqd", att.astype(qi.dtype), vi)
        qdecay = jnp.exp(cum)[..., None]
        inter = jnp.einsum(
            "bhqd,bhde->bhqe",
            (qi.astype(jnp.float32) * qdecay).astype(qi.dtype),
            C,
        )
        kdecay = jnp.exp(total - cum)[..., None] * ii[..., None]
        Cn = C * jnp.exp(total)[..., None].astype(C.dtype) + jnp.einsum(
            "bhkd,bhke->bhde",
            (ki.astype(jnp.float32) * kdecay).astype(ki.dtype),
            vi,
        )
        return Cn, intra + inter

    C0 = jnp.zeros((b, nh, dh, dh), x.dtype)
    _, out = jax.lax.scan(chunk_step, C0, (qc, kc, vc, fc, ic))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, nh, nc * chunk, dh)
    out = out[:, :, :s].transpose(0, 2, 1, 3).reshape(b, s, h)
    return x + (out @ p["mo"])


# -- RG-LRU (RecurrentGemma) ---------------------------------------------- #
def init_rglru(key, cfg, dtype):
    h = cfg["d_model"]
    d_r = cfg.get("lru_width") or h
    ks = jax.random.split(key, 6)
    sc = 1.0 / math.sqrt(h)
    return {
        "ln": jnp.zeros((h,), dtype),
        "rx": _normal(ks[0], (h, d_r), sc, dtype),
        "ry": _normal(ks[1], (h, d_r), sc, dtype),
        "ra": _normal(ks[2], (d_r, d_r), 1 / math.sqrt(d_r), dtype),
        "ri": _normal(ks[3], (d_r, d_r), 1 / math.sqrt(d_r), dtype),
        "lam": jnp.full((d_r,), 2.0, jnp.float32),
        "ro": _normal(ks[4], (d_r, h), sc / math.sqrt(2 * cfg["n_layers"]), dtype),
    }


def apply_rglru(p, x, cfg, ctx: ShardCtx):
    """Gated linear recurrence: h_t = a_t * h_{t-1} + gated_t.

    Two recurrence forms, selected by ``cfg["rglru_scan"]``:

      * ``"associative"`` (default): ``lax.associative_scan`` -- the
        TPU-parallel log-depth form.  Its backward is a log-depth
        slice/concat graph with *no* ``scan`` equation, so the recurrent
        B/W split (core/passes.py) cannot recurse into a body; the dp slice
        (the ``lam`` gate-scale grad) is instead handled by the generic
        byte-minimal cut -- the "scanified dp fallback" is simply not
        needing one.
      * ``"sequential"``: an explicit ``lax.scan`` over time.  This routes
        the recurrence through the scan-split path (dx-only B scan; any
        dp-only outputs replayed at W), and keeps the backward graph
        O(s) instead of O(s log s) -- preferable for very long sequences.
    """
    b, s, h = x.shape
    xin = rmsnorm(p["ln"], x)
    u = xin @ p["rx"]
    gate_y = jax.nn.gelu(xin @ p["ry"])
    r = jax.nn.sigmoid((u @ p["ra"]).astype(jnp.float32))
    i = jax.nn.sigmoid((u @ p["ri"]).astype(jnp.float32))
    log_a = -8.0 * jax.nn.softplus(p["lam"]) * r
    a = jnp.exp(log_a)
    gated = (jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * i) * u.astype(
        jnp.float32
    )

    if cfg.get("rglru_scan", "associative") == "sequential":
        def step(hc, ag):
            a_t, g_t = ag
            hn = a_t * hc + g_t
            return hn, hn

        _, hs_t = jax.lax.scan(
            step,
            jnp.zeros((b, a.shape[-1]), jnp.float32),
            (a.transpose(1, 0, 2), gated.transpose(1, 0, 2)),
        )
        hs = hs_t.transpose(1, 0, 2)
    else:
        def combine(l, r_):
            a1, h1 = l
            a2, h2 = r_
            return a1 * a2, a2 * h1 + h2

        _, hs = jax.lax.associative_scan(combine, (a, gated), axis=1)
    y = (hs.astype(x.dtype) * gate_y) @ p["ro"]
    return x + y


# -- encoder/decoder joint layer (Whisper; concat-carry) ------------------- #
def init_encdec(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    return {
        "enc_attn": init_attn(ks[0], cfg, dtype),
        "enc_mlp": init_mlp(ks[1], cfg, dtype),
        "dec_attn": init_attn(ks[2], cfg, dtype),
        "dec_mlp": init_mlp(ks[3], cfg, dtype),
        "xattn": init_attn(jax.random.fold_in(key, 9), cfg, dtype),
        "enc_on": jnp.ones((), dtype),
        "dec_on": jnp.ones((), dtype),
    }


def _attn_proj(p, cfg):
    return (
        p.get("wq", p.get("wq_rep")),
        p.get("wk", p.get("wk_rep")),
        p.get("wv", p.get("wv_rep")),
        p.get("wo", p.get("wo_rep")),
    )


def apply_encdec(p, x, positions, cfg, ctx: ShardCtx):
    """x = concat(enc_seq, dec_seq); per-stage masks pick enc / dec role."""
    s_enc = cfg["s_enc"]
    xe, xd = x[:, :s_enc], x[:, s_enc:]
    b = x.shape[0]
    tp = _tp(cfg)
    qs = _q_sharded(cfg)
    hq_l = cfg["n_heads"] // tp if qs else cfg["n_heads"]
    hk_l = cfg["n_kv_heads"] // tp if _kv_sharded(cfg) else cfg["n_kv_heads"]
    dh = cfg["d_model"] // cfg["n_heads"]
    pe, pd = positions[:s_enc], positions[: x.shape[1] - s_enc]

    def enc_f(xe):
        h = xe
        wq, wk, wv, wo = _attn_proj(p["enc_attn"], cfg)
        hin = rmsnorm(p["enc_attn"]["ln"], h)
        q = rope((hin @ wq).reshape(b, s_enc, hq_l, dh), pe)
        k = rope((hin @ wk).reshape(b, s_enc, hk_l, dh), pe)
        v = (hin @ wv).reshape(b, s_enc, hk_l, dh)
        k, v = _match_kv_heads(hq_l, k, v, cfg, ctx)
        o = attention(q, k, v, causal=False)
        o = o.reshape(b, s_enc, -1) @ wo
        h = h + (ctx.psum(o) if qs and tp > 1 else o)
        return apply_mlp(p["enc_mlp"], h, cfg, ctx)

    xe = xe + p["enc_on"] * (enc_f(xe) - xe)

    def dec_f(xd, xe):
        h = apply_attn(p["dec_attn"], xd, pd, cfg, ctx)
        wq, wk, wv, wo = _attn_proj(p["xattn"], cfg)
        hin = rmsnorm(p["xattn"]["ln"], h)
        sd = h.shape[1]
        q = (hin @ wq).reshape(b, sd, hq_l, dh)
        k = (xe @ wk).reshape(b, s_enc, hk_l, dh)
        v = (xe @ wv).reshape(b, s_enc, hk_l, dh)
        k, v = _match_kv_heads(hq_l, k, v, cfg, ctx)
        o = attention(q, k, v, causal=False)
        o = o.reshape(b, sd, -1) @ wo
        h = h + (ctx.psum(o) if qs and tp > 1 else o)
        return apply_mlp(p["dec_mlp"], h, cfg, ctx)

    xd = xd + p["dec_on"] * (dec_f(xd, xe) - xd)
    return jnp.concatenate([xe, xd], axis=1)


# --------------------------------------------------------------------- #
# registry
# --------------------------------------------------------------------- #
LAYER_KINDS: Dict[str, Tuple] = {
    "attn": (init_attn, lambda p, x, pos, cfg, ctx: apply_attn(p, x, pos, cfg, ctx)),
    "attn_local": (
        init_attn,
        lambda p, x, pos, cfg, ctx: apply_attn(
            p, x, pos, cfg, ctx, window=cfg.get("window", 4096)
        ),
    ),
    "mlp": (init_mlp, lambda p, x, pos, cfg, ctx: apply_mlp(p, x, cfg, ctx)),
    "mla": (init_mla, apply_mla),
    "moe": (init_moe, lambda p, x, pos, cfg, ctx: apply_moe(p, x, cfg, ctx)),
    "slstm": (init_slstm, lambda p, x, pos, cfg, ctx: apply_slstm(p, x, cfg, ctx)),
    "mlstm": (init_mlstm, lambda p, x, pos, cfg, ctx: apply_mlstm(p, x, cfg, ctx)),
    "rglru": (init_rglru, lambda p, x, pos, cfg, ctx: apply_rglru(p, x, cfg, ctx)),
    "encdec": (init_encdec, apply_encdec),
}


def init_layer(kind: str, key, cfg, ctx: ShardCtx, dtype):
    del ctx  # params are global-shaped; sharding comes from specs
    return LAYER_KINDS[kind][0](key, cfg, dtype)


def apply_layer(kind: str, params, x, positions, cfg, ctx: ShardCtx):
    return LAYER_KINDS[kind][1](params, x, positions, cfg, ctx)


def apply_block(
    kinds: Tuple[str, ...], mask, params, x, positions, cfg, ctx: ShardCtx
):
    """One architectural block (possibly several sub-kinds) with its padding
    mask folded in: padded blocks are exact no-ops with zero gradients.

    This is the unit the F/B/W split operates on: each block becomes its own
    split-VJP module (models/lm.py), so B emits a compact per-block M_W
    context -- the dgrad/wgrad pair of every kind falls out of the backward
    jaxpr partition in core/passes.py rather than a hand-written table.
    """
    xb = x
    for ki, kind in enumerate(kinds):
        xb = apply_layer(kind, params[ki], xb, positions, cfg, ctx)
    m = mask.astype(x.dtype)
    return m * xb + (1.0 - m) * x


# --------------------------------------------------------------------- #
# vocab-parallel cross entropy (sink)
# --------------------------------------------------------------------- #
def vocab_parallel_ce(logits_loc, labels, ctx: ShardCtx, vocab: int):
    """logits_loc: (b, s, V_pad/tp) this rank's vocab shard; labels: (b, s)."""
    v_l = logits_loc.shape[-1]
    off = ctx.index() * v_l
    z = logits_loc.astype(jnp.float32)
    zmax = ctx.pmax(jnp.max(z, axis=-1))  # gradient-free max-shift
    z = z - zmax[..., None]
    sumexp = ctx.psum(jnp.sum(jnp.exp(z), axis=-1))
    local_lab = labels - off
    in_range = (local_lab >= 0) & (local_lab < v_l)
    safe = jnp.clip(local_lab, 0, v_l - 1)
    picked = jnp.take_along_axis(z, safe[..., None], axis=-1)[..., 0]
    picked = ctx.psum(jnp.where(in_range, picked, 0.0))
    return jnp.mean(jnp.log(sumexp) - picked)
