"""Model assembly: ArchConfig -> PipelineProgram for the ZB executor.

A model is a stack of *blocks* (block = one architectural layer, possibly
several sub-kinds, e.g. ("attn", "mlp")), repeated over a pattern.  Blocks
are assigned to (stage, chunk) groups of uniform size and uniform pattern
phase, so every stage traces the *same* chunk function (an SPMD requirement;
see executor.py).  When ``n_layers`` doesn't divide evenly, groups are padded
with mask-disabled blocks: the mask rides in the (stage-varying) parameters
and multiplies the block output, so padded blocks are exact no-ops with zero
gradients; the trainer freezes mask leaves.

Embedding (vocab-parallel) + modality-frontend projections form the shared
``src``; final norm + vocab-parallel head + CE form the shared ``sink``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.executor import PipelineProgram
from ..core.passes import FBWModule, auto_fbw
from .modules import (
    LAYER_KINDS,
    ShardCtx,
    apply_block,
    apply_layer,
    init_layer,
    pad_to_multiple,
    rmsnorm,
    vocab_parallel_ce,
)

PyTree = Any

__all__ = [
    "ArchConfig",
    "RunSpec",
    "ChunkFBW",
    "build_program",
    "init_params",
    "layer_cfg",
]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    block_pattern: Tuple[Tuple[str, ...], ...] = (("attn", "mlp"),)
    head_dim: Optional[int] = None
    extras: Tuple[Tuple[str, Any], ...] = ()  # hashable dict
    dtype: str = "float32"
    sub_quadratic: bool = False  # eligible for long_500k decode
    has_decoder: bool = True  # False only for pure encoders
    source: str = ""  # provenance note

    def extras_dict(self) -> Dict[str, Any]:
        return dict(self.extras)

    @property
    def period(self) -> int:
        return len(self.block_pattern)

    def jdtype(self):
        return {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float64": jnp.float64}[
            self.dtype
        ]


@dataclasses.dataclass(frozen=True)
class RunSpec:
    p: int  # pipeline stages
    n_chunks: int  # chunks per stage (1, or 2 for ZB-V / interleaved)
    microbatch: int  # b per microbatch
    seq_len: int
    m: int  # number of microbatches per pipe
    tp_axis: Optional[str] = None
    tp_size: int = 1


def layer_cfg(cfg: ArchConfig, tp_size: int = 1) -> Dict[str, Any]:
    d = dict(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        d_ff=cfg.d_ff,
        n_layers=cfg.n_layers,
        head_dim=cfg.head_dim,
        tp_size=tp_size,
    )
    d.update(cfg.extras_dict())
    return d


# --------------------------------------------------------------------- #
# block -> group assignment
# --------------------------------------------------------------------- #
def group_layout(cfg: ArchConfig, p: int, n_chunks: int) -> Tuple[Tuple[Tuple[str, ...], ...], int]:
    """Blocks per (stage, chunk) group; returns (group pattern, group size).

    Group size g is the smallest multiple of the pattern period with
    g * p * n_chunks >= n_layers, so every group is pattern-aligned.
    """
    period = cfg.period
    slots = p * n_chunks
    g = max(1, math.ceil(cfg.n_layers / slots))
    g = period * math.ceil(g / period)
    blocks = tuple(cfg.block_pattern[i % period] for i in range(g))
    return blocks, g


def group_positions(p: int, n_chunks: int, placement) -> "np.ndarray":
    """(p, n_chunks) int: each (stage, chunk) group's position along the
    model depth (chunk c, k-th stage on its path -> c * p + k)."""
    import numpy as np

    pos = np.zeros((p, n_chunks), np.int64)
    for c in range(n_chunks):
        for k in range(p):
            pos[placement.stage_of(c, k), c] = c * p + k
    return pos


def group_masks(cfg: ArchConfig, p: int, n_chunks: int, placement) -> "np.ndarray":
    """(p, n_chunks, g) float mask: 1 for real blocks, 0 for padding."""
    import numpy as np

    _, g = group_layout(cfg, p, n_chunks)
    layer = group_positions(p, n_chunks, placement)[..., None] * g + np.arange(g)
    return (layer < cfg.n_layers).astype(np.float32)


# --------------------------------------------------------------------- #
# chunk modules: one split-VJP module per architectural block
# --------------------------------------------------------------------- #
def make_chunk_fn(cfg: ArchConfig, p: int, n_chunks: int, ctx: ShardCtx):
    """Whole-chunk forward (reference path; the executor uses ChunkFBW)."""
    blocks, g = group_layout(cfg, p, n_chunks)
    lcfg = layer_cfg(cfg, ctx.tp_size)

    def chunk_fn(params, x, side):
        pos = side["positions"]
        for bi, kinds in enumerate(blocks):
            x = apply_block(
                kinds, params["mask"][bi], params["blocks"][bi], x, pos, lcfg, ctx
            )
        return x

    return chunk_fn, blocks, g


class ChunkFBW(FBWModule):
    """A pipeline chunk as a sequence of per-block split-VJP modules.

    The executor-facing param structure is unchanged (``{"mask": (g,),
    "blocks": (...)}`` -- checkpoints, sharding rules and the optimizer's
    mask freeze are untouched); each block module sees the slice
    ``(mask[bi], blocks[bi])``.  B consumes the block residuals
    right-to-left and emits one compact M_W context per block (the paper's
    per-block kept cotangents + wgrad inputs); W reassembles the chunk
    gradient from those contexts alone.
    """

    def __init__(
        self,
        cfg: ArchConfig,
        p: int,
        n_chunks: int,
        ctx: ShardCtx,
        name: str,
        compact: Optional[bool] = None,
    ):
        blocks, g = group_layout(cfg, p, n_chunks)
        lcfg = layer_cfg(cfg, ctx.tp_size)
        self.name = name
        self.block_kinds = blocks

        def block_fn(kinds):
            def f(params, x, side):
                mask, kp = params
                return apply_block(kinds, mask, kp, x, side["positions"], lcfg, ctx)

            return f

        self.mods = [
            auto_fbw(block_fn(kinds), name=f"{name}.b{bi}", compact=compact)
            for bi, kinds in enumerate(blocks)
        ]

    @staticmethod
    def _bp(params, bi):
        return (params["mask"][bi], params["blocks"][bi])

    def fwd(self, params, x, side):
        res_all = []
        for bi, mod in enumerate(self.mods):
            x, res = mod.fwd(self._bp(params, bi), x, side)
            res_all.append(res)
        return x, tuple(res_all)

    def bwd_x(self, params, res, dy, side):
        wctx_all = [None] * len(self.mods)
        for bi in reversed(range(len(self.mods))):
            dy, w = self.mods[bi].bwd_x(self._bp(params, bi), res[bi], dy, side)
            wctx_all[bi] = w
        return dy, tuple(wctx_all)

    def bwd_w(self, params, wctx, side, acc=None):
        outs = []
        for bi, mod in enumerate(self.mods):
            a = None if acc is None else (acc["mask"][bi], acc["blocks"][bi])
            outs.append(mod.bwd_w(self._bp(params, bi), wctx[bi], side, acc=a))
        return {
            "mask": jnp.stack([o[0] for o in outs]),
            "blocks": tuple(o[1] for o in outs),
        }

    def ensure_traced(self, params, x, side) -> None:
        jax.eval_shape(lambda p, xx, sd: self.fwd(p, xx, sd), params, x, side)


def init_chunk_params(cfg: ArchConfig, key, first_layer: int, blocks, ctx: ShardCtx, mask):
    """One group's params.  Each block's key is its global layer index, so a
    layer gets the same weights under every placement (1F1B, ZB-V, ...)."""
    lcfg = layer_cfg(cfg, ctx.tp_size)
    dt = cfg.jdtype()
    block_params = []
    for bi, kinds in enumerate(blocks):
        kp = []
        for ki, kind in enumerate(kinds):
            sub = jax.random.fold_in(key, (first_layer + bi) * 13 + ki)
            kp.append(init_layer(kind, sub, lcfg, ctx, dt))
        block_params.append(tuple(kp))
    return {
        "mask": jnp.asarray(mask, jnp.float32),
        "blocks": tuple(block_params),
    }


# --------------------------------------------------------------------- #
# src (embedding + frontend) and sink (norm + head + CE)
# --------------------------------------------------------------------- #
def init_shared(cfg: ArchConfig, key, ctx: ShardCtx):
    dt = cfg.jdtype()
    v_pad = pad_to_multiple(cfg.vocab, max(1, ctx.tp_size))
    ks = jax.random.split(key, 4)
    shared = {
        "embed": (jax.random.normal(ks[0], (v_pad, cfg.d_model)) * 0.02).astype(dt),
        "head": (jax.random.normal(ks[1], (cfg.d_model, v_pad)) * 0.02).astype(dt),
        "final_ln": jnp.zeros((cfg.d_model,), dt),
    }
    if cfg.family in ("encdec", "vlm"):
        d_front = cfg.extras_dict().get("frontend_dim", cfg.d_model)
        shared["front_proj"] = (
            jax.random.normal(ks[2], (d_front, cfg.d_model)) * 0.02
        ).astype(dt)
    return shared


def _embed_lookup(shared, tokens, cfg: ArchConfig, ctx: ShardCtx):
    v_l = shared["embed"].shape[0]
    off = ctx.index() * v_l
    loc = tokens - off
    ok = (loc >= 0) & (loc < v_l)
    safe = jnp.clip(loc, 0, v_l - 1)
    x = shared["embed"][safe] * ok[..., None].astype(shared["embed"].dtype)
    return ctx.psum(x) if ctx.tp_axis else x


def _embed_grad(shared, tokens, dx, ctx: ShardCtx):
    v_l = shared["embed"].shape[0]
    off = ctx.index() * v_l
    loc = tokens - off
    ok = (loc >= 0) & (loc < v_l)
    safe = jnp.clip(loc, 0, v_l - 1)
    flat_tok = safe.reshape(-1)
    flat_dx = (dx * ok[..., None].astype(dx.dtype)).reshape(-1, dx.shape[-1])
    g = jnp.zeros_like(shared["embed"], dtype=jnp.promote_types(dx.dtype, jnp.float32))
    return g.at[flat_tok].add(flat_dx.astype(g.dtype))


def make_src(cfg: ArchConfig, ctx: ShardCtx):
    fam = cfg.family
    dt = cfg.jdtype()

    def src_fwd(shared, side_mb):
        tok = side_mb["tokens"]
        x = _embed_lookup(shared, tok, cfg, ctx)
        if fam == "encdec":
            front = side_mb["frames"].astype(dt) @ shared["front_proj"]
            x = jnp.concatenate([front, x], axis=1)
        elif fam == "vlm":
            front = side_mb["patches"].astype(dt) @ shared["front_proj"]
            x = jnp.concatenate([front, x], axis=1)
        return x

    def src_bwd_w(shared, side_mb, dx):
        g = {k: jnp.zeros_like(v, dtype=jnp.float32) for k, v in shared.items()}
        tok = side_mb["tokens"]
        if fam == "encdec":
            nf = side_mb["frames"].shape[1]
            dfront, dtok = dx[:, :nf], dx[:, nf:]
            fr = side_mb["frames"].astype(jnp.float32)
            g["front_proj"] = jnp.einsum("bsf,bsh->fh", fr, dfront.astype(jnp.float32))
        elif fam == "vlm":
            nf = side_mb["patches"].shape[1]
            dfront, dtok = dx[:, :nf], dx[:, nf:]
            fr = side_mb["patches"].astype(jnp.float32)
            g["front_proj"] = jnp.einsum("bsf,bsh->fh", fr, dfront.astype(jnp.float32))
        else:
            dtok = dx
        g["embed"] = _embed_grad(shared, tok, dtok, ctx)
        return g

    return src_fwd, src_bwd_w


def make_sink_fn(cfg: ArchConfig, ctx: ShardCtx, m: int):
    fam = cfg.family

    def sink_fn(shared, y, side_mb):
        if fam == "encdec":
            y = y[:, side_mb["frames"].shape[1] :]
        elif fam == "vlm":
            y = y[:, side_mb["patches"].shape[1] :]
        yn = rmsnorm(shared["final_ln"], y)
        logits = yn @ shared["head"]
        loss = vocab_parallel_ce(logits, side_mb["labels"], ctx, cfg.vocab)
        return loss / m

    return sink_fn


# --------------------------------------------------------------------- #
# program factory
# --------------------------------------------------------------------- #
def build_program(
    cfg: ArchConfig,
    spec: RunSpec,
    placement,
    compact: Optional[bool] = None,
    platform: Optional[str] = None,
) -> PipelineProgram:
    """``compact`` selects the byte-minimal W-context split (core/passes);
    the default follows ``auto_fbw``'s global default.  ``compact=False``
    is the whole-scan-in-B / frontier-cut baseline the measured-memory
    tests compare against.  ``platform`` is that of the devices the step
    runs on (None: the process's default backend); it picks the attention
    kernel (``modules.attention``)."""
    ctx = ShardCtx(tp_axis=spec.tp_axis, tp_size=spec.tp_size, platform=platform)
    src_fwd, src_bwd_w = make_src(cfg, ctx)
    sink_fn = make_sink_fn(cfg, ctx, spec.m)

    s_total = spec.seq_len
    if cfg.family == "encdec":
        s_total = cfg.extras_dict()["s_enc"] + spec.seq_len
    elif cfg.family == "vlm":
        s_total = cfg.extras_dict()["n_patches"] + spec.seq_len

    chunks = [
        ChunkFBW(
            cfg, spec.p, spec.n_chunks, ctx,
            name=f"{cfg.name}.chunk{c}", compact=compact,
        )
        for c in range(spec.n_chunks)
    ]
    return PipelineProgram(
        chunks=chunks,
        src_fwd=src_fwd,
        src_bwd_w=src_bwd_w,
        sink=auto_fbw(sink_fn, name=f"{cfg.name}.sink", compact=compact),
        act_shape=(spec.microbatch, s_total, cfg.d_model),
        act_dtype=cfg.jdtype(),
    )


def init_params(cfg: ArchConfig, spec: RunSpec, placement, key=None):
    """Returns (stacked_stage_params per chunk, shared params)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    ctx = ShardCtx(tp_axis=spec.tp_axis, tp_size=spec.tp_size)
    blocks, g = group_layout(cfg, spec.p, spec.n_chunks)
    pos = group_positions(spec.p, spec.n_chunks, placement)
    masks = group_masks(cfg, spec.p, spec.n_chunks, placement)
    stacked = []
    for c in range(spec.n_chunks):
        per_stage = [
            init_chunk_params(cfg, key, int(pos[s, c]) * g, blocks, ctx, masks[s, c])
            for s in range(spec.p)
        ]
        stacked.append(
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage)
        )
    shared = init_shared(cfg, jax.random.fold_in(key, 999), ctx)
    return tuple(stacked), shared


def side_inputs(cfg: ArchConfig, spec: RunSpec, key=None):
    """Synthetic per-microbatch side inputs: tokens, labels, positions."""
    key = key if key is not None else jax.random.PRNGKey(1)
    m, b, s = spec.m, spec.microbatch, spec.seq_len
    ks = jax.random.split(key, 4)
    side = {
        "tokens": jax.random.randint(ks[0], (m, b, s), 0, cfg.vocab),
        "labels": jax.random.randint(ks[1], (m, b, s), 0, cfg.vocab),
    }
    s_total = s
    ex = cfg.extras_dict()
    if cfg.family == "encdec":
        side["frames"] = jax.random.normal(
            ks[2], (m, b, ex["s_enc"], ex.get("frontend_dim", cfg.d_model))
        ).astype(cfg.jdtype())
        s_total = ex["s_enc"] + s
    elif cfg.family == "vlm":
        side["patches"] = jax.random.normal(
            ks[2], (m, b, ex["n_patches"], ex.get("frontend_dim", cfg.d_model))
        ).astype(cfg.jdtype())
        s_total = ex["n_patches"] + s
    side["positions"] = jnp.broadcast_to(jnp.arange(s_total), (m, s_total))
    return side
