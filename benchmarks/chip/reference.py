"""The plain reference: the configuration's training step in float32.

Straight ``jax.numpy``, under ``jax.default_matmul_precision("highest")``,
written from the architecture's description and the configuration file; it
imports nothing of the program.  The embedding, the final norm, the head
and the loss, the optimizer and the run are the same for every
architecture; a layer is its architecture's (``archs/<architecture>.py``
``layer(ref, w, x, pos)``), built from this class's primitives: ``mm``,
``rmsnorm``, ``rope`` and ``attend``.  One step is the mean next-token cross
entropy over every row of the batch, its gradient, a global-norm clip and
AdamW with weight decay on every tensor.  Memory is kept in bounds by
recomputing each layer in the backward pass, attending in blocks of queries
and taking the head and the loss in blocks of rows.

``quant="fp8"`` is the control: the same step with both operands of every
matrix product rounded to fp8 (e4m3, 3 mantissa bits) under a per-tensor
scale, the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from weights import layer_rows, plain_shapes, plain_weights

Q_BLOCK = 1024  # queries attended at a time
ROW_BLOCK = 1024  # rows of the head and the loss at a time
E4M3_MAX = 240.0  # largest finite e4m3 value of reduce_precision(4, 3)


def _fp8(x):
    """Round to e4m3 under a per-tensor scale; the gradient passes straight."""
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / E4M3_MAX + 1e-30)
    q = jax.lax.reduce_precision(x / s, exponent_bits=4, mantissa_bits=3) * s
    return x + jax.lax.stop_gradient(q - x)


class Reference:
    def __init__(self, arch, config: Dict[str, Any], optimizer: Dict[str, float], quant: Optional[str] = None):
        if quant not in (None, "fp8"):
            raise ValueError(f"unknown quant {quant!r}")
        self.arch = arch
        self.config = config
        self.rows = layer_rows(arch, config)  # layer leaf -> {layer: row}
        self.eps = float(config["rms_norm_eps"])
        self.theta = float(config["rope_theta"])
        self.opt = optimizer
        self.q = _fp8 if quant == "fp8" else (lambda x: x)

    # ----------------------------------------------------------------- #
    # model
    # ----------------------------------------------------------------- #
    def mm(self, spec, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b))

    def rmsnorm(self, x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.eps) * (1.0 + g)

    def rope(self, x, pos):
        half = x.shape[-1] // 2
        freq = jnp.exp(-math.log(self.theta) * jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos[:, None].astype(jnp.float32) * freq  # (s, half)
        cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)

    def attend(self, q, k, v):
        """Causal softmax attention, one block of queries at a time; the
        values' head dim may differ from the queries' and keys'."""
        b, s, hq, dh = q.shape
        dv = v.shape[-1]
        blk = min(Q_BLOCK, s)
        nb = s // blk
        qb = q.reshape(b, nb, blk, hq, dh).transpose(1, 0, 2, 3, 4)
        kpos = jnp.arange(s)

        @jax.checkpoint
        def one(qi, i):
            z = self.mm("bqhd,bkhd->bhqk", qi, k) / math.sqrt(dh)
            qpos = i * blk + jnp.arange(blk)
            z = jnp.where(kpos[None, :] <= qpos[:, None], z, -jnp.inf)
            return self.mm("bhqk,bkhd->bqhd", jax.nn.softmax(z, axis=-1), v)

        _, out = jax.lax.scan(lambda c, a: (c, one(*a)), None, (qb, jnp.arange(nb)))
        return out.transpose(1, 0, 2, 3, 4).reshape(b, s, hq, dv)

    def loss_sum(self, params, tokens, labels):
        """Sum of the cross entropy over the rows of one microbatch."""
        b, s = tokens.shape
        pos = jnp.arange(s)
        x = params["embed"][tokens]
        layer = functools.partial(self.arch.layer, self)
        for li in range(self.config["num_hidden_layers"]):
            w = {n: params[n][self.rows[n][li]] for n in self.arch.layer_leaves(self.config, li)}
            x = jax.checkpoint(layer)(w, x, pos)
        y = self.rmsnorm(x, params["final_norm"]).reshape(b * s, -1)
        lab = labels.reshape(b * s)
        blk = min(ROW_BLOCK, b * s)

        @jax.checkpoint
        def rows(yi, li):
            z = self.mm("rh,hv->rv", yi, params["head"])
            lse = jax.nn.logsumexp(z, axis=-1)
            return jnp.sum(lse - jnp.take_along_axis(z, li[:, None], axis=-1)[:, 0])

        nb = (b * s) // blk
        _, part = jax.lax.scan(
            lambda c, a: (c, rows(*a)),
            None,
            (y.reshape(nb, blk, -1), lab.reshape(nb, blk)),
        )
        return jnp.sum(part)

    def loss_and_grad(self, params, tokens, labels):
        """Mean loss over the batch (m, b, s) and its gradient."""
        n = tokens.size

        def total(pr):
            def body(acc, tl):
                return acc + self.loss_sum(pr, *tl), None

            acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (tokens, labels))
            return acc / n

        return jax.value_and_grad(total)(params)

    # ----------------------------------------------------------------- #
    # optimizer
    # ----------------------------------------------------------------- #
    def step(self, params, m, v, t, tokens, labels):
        """One training step; returns the new state, the loss, the global
        gradient norm before the clip and each leaf's norm of the clipped
        gradient ((L,) for layer leaves)."""
        o = self.opt
        with jax.default_matmul_precision("highest"):
            loss, g = self.loss_and_grad(params, tokens, labels)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, o["grad_clip"] / gnorm) if o.get("grad_clip") else 1.0
        t = t + 1
        b1, b2 = o["b1"], o["b2"]
        new_p, new_m, new_v, leaf_norms = {}, {}, {}, {}
        for name in params:
            gi = g[name] * scale
            leaf_norms[name] = _leaf_norm(gi, name in self.rows)
            mi = b1 * m[name] + (1 - b1) * gi
            vi = b2 * v[name] + (1 - b2) * gi * gi
            mhat, vhat = mi / (1 - b1**t), vi / (1 - b2**t)
            p = params[name]
            new_p[name] = p - o["lr"] * o["weight_decay"] * p - o["lr"] * mhat / (jnp.sqrt(vhat) + o["eps"])
            new_m[name], new_v[name] = mi, vi
        return new_p, new_m, new_v, t, loss, gnorm, leaf_norms

    # ----------------------------------------------------------------- #
    # a run of k steps from the seed's weights
    # ----------------------------------------------------------------- #
    def run(self, key_words, batches, devices) -> Dict[str, Any]:
        """Follow the program's first ``len(batches)`` steps.

        Returns the losses and pre-clip gradient norms of every step, the
        per-leaf norms of the first step's clipped gradient and of the change
        of the weights over all the steps.  State lives in float32, spread
        over ``devices`` (each tensor split along a dimension they divide)."""
        mesh = jax.make_mesh(
            (len(devices),), ("r",), devices=devices, axis_types=(AxisType.Auto,)
        )
        shapes = plain_shapes(self.arch, self.config)
        shard = {k: NamedSharding(mesh, _split_spec(s, len(devices))) for k, s in shapes.items()}
        rep = NamedSharding(mesh, P())
        arch, cfg = self.arch, self.config

        init = jax.jit(
            lambda kw: jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), plain_weights(arch, kw, cfg)
            ),
            out_shardings=shard,
        )
        zeros = jax.jit(
            lambda: {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()},
            out_shardings=shard,
        )
        step = jax.jit(
            self.step,
            in_shardings=(shard, shard, shard, rep, rep, rep),
            out_shardings=(shard, shard, shard, rep, rep, rep, rep),
            donate_argnums=(0, 1, 2),
        )
        kw = jnp.asarray(key_words)
        params, m, v, t = init(kw), zeros(), zeros(), jnp.zeros((), jnp.int32)
        losses, gnorms, g1 = [], [], None
        for b in batches:
            tok = jnp.asarray(b["tokens"])
            lab = jnp.asarray(b["labels"])
            params, m, v, t, loss, gnorm, leaf = step(params, m, v, t, tok, lab)
            losses.append(float(loss))
            gnorms.append(float(gnorm))
            if g1 is None:
                g1 = jax.tree_util.tree_map(np.asarray, leaf)
        del m, v
        change = jax.jit(
            lambda p, kw: {k: _leaf_norm(p[k] - p0, k in self.rows) for k, p0 in init(kw).items()},
            out_shardings=rep,
        )(params, kw)
        return {
            "losses": losses,
            "grad_norms": gnorms,
            "grad_leaf": g1,
            "change_leaf": jax.tree_util.tree_map(np.asarray, change),
        }


def _leaf_norm(x, per_layer: bool):
    if per_layer:  # one norm a layer
        return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
    return jnp.sqrt(jnp.sum(x * x))


def _split_spec(shape, n: int) -> P:
    """Split the largest dimension that ``n`` divides, else replicate."""
    if n == 1:
        return P()
    best = None
    for i, s in enumerate(shape):
        if s % n == 0 and (best is None or s > shape[best]):
            best = i
    if best is None:
        return P()
    return P(*([None] * best + ["r"]))
