"""Causal self-attention as a blocked online-softmax Pallas kernel (TPU).

The sequence is cut into square tiles of ``block`` rows.  Each (query tile,
key tile) step keeps its softmax state in VMEM, so no (s, s) score tensor
reaches HBM, and the grid walks only the tiles on or below the causal
diagonal (scalar-prefetched tile tables), so tiles above it cost neither
a DMA nor a step.  Grouped kv heads (``hq % hk == 0``) are read in place:
q head ``h`` reads kv head ``h // (hq // hk)``, with no repeat.

Three kernels: the forward (output and per-row log-sum-exp), dq (a query
tile against its keys) and dk/dv (a key tile against the queries of every
q head of its group).  The backward recomputes the scores from q, k and
the saved log-sum-exp.  Numerics match the jnp path: bf16 inputs, f32
scores and accumulation, exact causal softmax.

``kernel_block`` says whether the kernel takes an input, from what the
caller observes (platform, mask, shapes); ``causal_attention`` runs it on
the model's ``(b, s, h, d)`` layout.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["PLATFORM", "kernel_block", "causal_attention"]

#: the platform the kernel compiles for
PLATFORM = "tpu"
#: largest tile; a sequence is cut into at least _MIN_BLOCKS tiles, so the
#: causal mask has whole tiles to skip
_BLOCK = 512
_MIN_BLOCKS = 4
_LANES = 128
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_MASKED = -0.7 * float(np.finfo(np.float32).max)
_GRID = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def kernel_block(q_shape, k_shape, v_shape, *, causal, window, softcap, platform):
    """The tile the kernel would run ``(b, s, h, d)`` inputs with, or None
    where it does not take them (the caller then stays on jnp)."""
    _, s, hq, d = q_shape
    _, sk, hk, dk = k_shape
    if platform != PLATFORM or not causal or window is not None or softcap is not None:
        return None
    if sk != s or dk != d or v_shape != k_shape or d % _LANES or hq % hk:
        return None
    for block in range(_BLOCK, _LANES - 1, -_LANES):
        if s % block == 0 and s >= _MIN_BLOCKS * block:
            return block
    return None


# --------------------------------------------------------------------- #
# tile tables: the grid's last axis walks these (i: query tile, j: key tile)
# --------------------------------------------------------------------- #
def _rows(n):
    """(i, j) for j <= i, row by row: a query tile's keys, diagonal last."""
    i, j = np.tril_indices(n)
    return jnp.asarray(i, jnp.int32), jnp.asarray(j, jnp.int32)


def _columns(n, group):
    """(j, r, i) for i >= j: a key tile against the query tiles of each of
    its group's q heads ``r``, diagonal first."""
    t = np.array([(j, r, i) for j in range(n) for r in range(group) for i in range(j, n)])
    return tuple(jnp.asarray(c, jnp.int32) for c in t.T)


def _lanes(x, n):
    """(rows, 128) with every lane equal -> (rows, n)."""
    return x if n == _LANES else jnp.tile(x, (1, n // _LANES))


def _causal(shape, keys_in_rows=False):
    """Within a diagonal tile: key position <= query position."""
    rows = lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = lax.broadcasted_iota(jnp.int32, shape, 1)
    return rows <= cols if keys_in_rows else cols <= rows


# --------------------------------------------------------------------- #
# kernels; refs are one (block, d) tile, or a (1, block) row of a per-row
# statistic, of one batch row and head
# --------------------------------------------------------------------- #
def _fwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref):
    t = pl.program_id(2)
    i, j = qi_ref[t], kj_ref[t]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def tile(diagonal):
        s = lax.dot_general(q_ref[...], k_ref[...], _NT, preferred_element_type=jnp.float32)
        if diagonal:
            s = jnp.where(_causal(s.shape), s, _MASKED)
        m_prev = m_ref[...]  # (block, 128), each lane the row's running max
        m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, s.shape[1]))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_next
        v = v_ref[...]
        pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * _lanes(alpha, v.shape[1]) + pv

    pl.when(j < i)(functools.partial(tile, False))

    @pl.when(j == i)
    def _():
        tile(True)
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / _lanes(l, acc_ref.shape[1])).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _dq_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_ref):
    t = pl.program_id(2)
    i, j = qi_ref[t], kj_ref[t]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def tile(diagonal):
        k = k_ref[...]
        s = lax.dot_general(q_ref[...], k, _NT, preferred_element_type=jnp.float32)
        if diagonal:
            s = jnp.where(_causal(s.shape), s, _MASKED)
        p = jnp.exp(s - lse_ref[0][:, None])
        dp = lax.dot_general(do_ref[...], v_ref[...], _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0][:, None])
        acc_ref[...] += jnp.dot(ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    pl.when(j < i)(functools.partial(tile, False))

    @pl.when(j == i)
    def _():
        tile(True)
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(kj_ref, qr_ref, qi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, group, n):
    # scores transposed, (keys, queries): the per-row statistics broadcast
    # as rows and every product is a plain or an NT matmul
    t = pl.program_id(2)
    j, r, i = kj_ref[t], qr_ref[t], qi_ref[t]

    @pl.when((r == 0) & (i == j))
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    def tile(diagonal):
        q, do = q_ref[...], do_ref[...]
        st = lax.dot_general(k_ref[...], q, _NT, preferred_element_type=jnp.float32)
        if diagonal:
            st = jnp.where(_causal(st.shape, keys_in_rows=True), st, _MASKED)
        pt = jnp.exp(st - lse_ref[...])
        dv_acc[...] += jnp.dot(pt.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_ref[...], do, _NT, preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[...])
        dk_acc[...] += jnp.dot(dst.astype(q.dtype), q, preferred_element_type=jnp.float32)

    pl.when(i > j)(functools.partial(tile, False))
    pl.when(i == j)(functools.partial(tile, True))

    @pl.when((r == group - 1) & (i == n - 1))
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


# --------------------------------------------------------------------- #
# calls, on heads-first (b, h, s, d) arrays
# --------------------------------------------------------------------- #
def _tile_spec(block, d, index):
    return pl.BlockSpec((None, None, block, d), index)


def _row_spec(block, index):
    return pl.BlockSpec((None, None, 1, block), index)


def _forward(q, k, v, block, interpret):
    b, hq, s, d = q.shape
    group = hq // k.shape[1]
    qi, kj = _rows(s // block)
    q_at = lambda b_, h, t, qi, kj: (b_, h, qi[t], 0)
    kv_at = lambda b_, h, t, qi, kj: (b_, h // group, kj[t], 0)
    o, lse = pl.pallas_call(
        _fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hq, qi.shape[0]),
            in_specs=[_tile_spec(block, d, q_at), _tile_spec(block, d, kv_at),
                      _tile_spec(block, d, kv_at)],
            out_specs=[_tile_spec(block, d, q_at), _tile_spec(block, _LANES, q_at)],
            scratch_shapes=[pltpu.VMEM((block, _LANES), jnp.float32),
                            pltpu.VMEM((block, _LANES), jnp.float32),
                            pltpu.VMEM((block, d), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, hq, s, _LANES), jnp.float32)],
        compiler_params=_GRID,
        name="flash_fwd",
        interpret=interpret,
    )(qi, kj, q, k, v)
    return o, lse[..., :1].reshape(b, hq, 1, s)


def _backward(q, k, v, do, lse, di, block, interpret):
    b, hq, s, d = q.shape
    hk = k.shape[1]
    group, n = hq // hk, s // block
    qi, kj = _rows(n)
    q_at = lambda b_, h, t, qi, kj: (b_, h, qi[t], 0)
    kv_at = lambda b_, h, t, qi, kj: (b_, h // group, kj[t], 0)
    row_at = lambda b_, h, t, qi, kj: (b_, h, 0, qi[t])
    dq = pl.pallas_call(
        _dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hq, qi.shape[0]),
            in_specs=[_tile_spec(block, d, q_at), _tile_spec(block, d, kv_at),
                      _tile_spec(block, d, kv_at), _tile_spec(block, d, q_at),
                      _row_spec(block, row_at), _row_spec(block, row_at)],
            out_specs=_tile_spec(block, d, q_at),
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_GRID,
        name="flash_dq",
        interpret=interpret,
    )(qi, kj, q, k, v, do, lse, di)

    cj, cr, ci = _columns(n, group)
    q_of = lambda b_, h, t, cj, cr, ci: (b_, h * group + cr[t], ci[t], 0)
    kv_of = lambda b_, h, t, cj, cr, ci: (b_, h, cj[t], 0)
    row_of = lambda b_, h, t, cj, cr, ci: (b_, h * group + cr[t], 0, ci[t])
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, group=group, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hk, cj.shape[0]),
            in_specs=[_tile_spec(block, d, q_of), _tile_spec(block, d, kv_of),
                      _tile_spec(block, d, kv_of), _tile_spec(block, d, q_of),
                      _row_spec(block, row_of), _row_spec(block, row_of)],
            out_specs=[_tile_spec(block, d, kv_of), _tile_spec(block, d, kv_of)],
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                            pltpu.VMEM((block, d), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_GRID,
        name="flash_dkv",
        interpret=interpret,
    )(cj, cr, ci, q, k, v, do, lse, di)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attend(q, k, v, block, interpret):
    return _forward(q, k, v, block, interpret)[0]


def _attend_fwd(q, k, v, block, interpret):
    o, lse = _forward(q, k, v, block, interpret)
    return o, (q, k, v, o, lse)


def _attend_bwd(block, interpret, res, do):
    q, k, v, o, lse = res
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = di.reshape(lse.shape)
    return _backward(q, k, v, do.astype(q.dtype), lse, di, block, interpret)


_attend.defvjp(_attend_fwd, _attend_bwd)


def causal_attention(q, k, v, block, *, interpret=False):
    """q: (b, s, hq, d); k, v: (b, s, hk, d) with hq % hk == 0 -> (b, s, hq, d).

    ``interpret`` runs the Pallas interpreter (CPU tests only)."""
    d = q.shape[-1]
    # the 1/sqrt(d) of the scores, applied to q in f32 and rounded once
    q = (q.astype(jnp.float32) * (1.0 / math.sqrt(d))).astype(q.dtype)
    heads_first = lambda x: x.transpose(0, 2, 1, 3)  # (b, h, s, d)
    with jax.named_scope("flash"):
        o = _attend(heads_first(q), heads_first(k), heads_first(v), block, interpret)
    return heads_first(o)
