"""Weights from the seed, in the plain layout and in the program's.

The benchmark makes the weights; neither the program nor the reference
does.  ``plain_weights`` draws them in one layout that the reference reads
directly (per-layer tensors stacked on a leading layer axis).
``ProgramLayout`` places the same tensors where the program's train step
takes them (stage-stacked chunks of blocks, shared embedding and head,
zero AdamW moments) and reads the program's state back per layer, so that
the check can compare each layer of each stage with the reference.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# plain name -> (program kind, program key); a block is ("attn", "mlp")
LAYER_LEAVES = {
    "attn_norm": ("attn", "ln"),
    "wq": ("attn", "wq"),
    "wk": ("attn", "wk"),
    "wv": ("attn", "wv"),
    "wo": ("attn", "wo"),
    "mlp_norm": ("mlp", "ln"),
    "w_up": ("mlp", "wu"),
    "w_gate": ("mlp", "wg"),
    "w_down": ("mlp", "wd"),
}
SHARED_LEAVES = {"embed": "embed", "head": "head", "final_norm": "final_ln"}
KINDS = ("attn", "mlp")


def dims(config: Dict[str, Any]) -> Dict[str, int]:
    h, hq = config["hidden_size"], config["num_attention_heads"]
    return dict(
        L=config["num_hidden_layers"],
        h=h,
        f=config["intermediate_size"],
        V=config["vocab_size"],
        hq=hq,
        hk=config["num_key_value_heads"],
        dh=config.get("head_dim") or h // hq,
    )


def plain_shapes(config: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    d = dims(config)
    L, h, f, V, hq, hk, dh = (d[k] for k in ("L", "h", "f", "V", "hq", "hk", "dh"))
    return {
        "embed": (V, h),
        "head": (h, V),
        "final_norm": (h,),
        "attn_norm": (L, h),
        "wq": (L, h, hq * dh),
        "wk": (L, h, hk * dh),
        "wv": (L, h, hk * dh),
        "wo": (L, hq * dh, h),
        "mlp_norm": (L, h),
        "w_up": (L, h, f),
        "w_gate": (L, h, f),
        "w_down": (L, f, h),
    }


def plain_weights(key_words, config: Dict[str, Any]) -> Dict[str, jax.Array]:
    """Every tensor, in the configuration's dtype: matrices normal(0,
    initializer_range), norm gains 0 (offsets from 1).  Traceable; the key is
    an argument, so one compiled program serves every seed."""
    dt = jnp.dtype(config["torch_dtype"])
    std = config["initializer_range"]
    key = jax.random.wrap_key_data(key_words.astype(jnp.uint32))
    out = {}
    for i, (name, shape) in enumerate(sorted(plain_shapes(config).items())):
        if name.endswith("norm"):
            out[name] = jnp.zeros(shape, dt)
        else:
            k = jax.random.fold_in(key, i)
            out[name] = (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)
    return out


class ProgramLayout:
    """Where each layer of the plain layout sits in the program's state."""

    def __init__(self, config: Dict[str, Any], setup):
        self.L = config["num_hidden_layers"]
        spec, placement = setup.spec, setup.sched.placement
        p, nc = spec.p, spec.n_chunks
        self.p, self.nc = p, nc
        self.g = math.ceil(self.L / (p * nc))
        pos = np.zeros((p, nc), np.int64)  # a group's place along the depth
        for c in range(nc):
            for k in range(p):
                pos[placement.stage_of(c, k), c] = c * p + k
        # layer[c][bi] -> (p,) global layer of block bi of chunk c, per stage
        self.layer = [[pos[:, c] * self.g + bi for bi in range(self.g)] for c in range(nc)]
        self.abstract = jax.eval_shape(setup.init_state, 0)
        self.shardings = setup.state_shardings

    def _masks(self) -> List[np.ndarray]:
        return [
            np.stack([self.layer[c][bi] < self.L for bi in range(self.g)], axis=1).astype(
                np.float32
            )
            for c in range(self.nc)
        ]

    def params(self, plain: Dict[str, jax.Array]):
        """(stacked, shared) in the program's structure, from plain tensors."""
        masks = self._masks()
        stacked = []
        for c in range(self.nc):
            blocks = []
            for bi in range(self.g):
                idx = np.minimum(self.layer[c][bi], self.L - 1)  # padding is masked
                kinds = {k: {} for k in KINDS}
                for name, (kind, key) in LAYER_LEAVES.items():
                    kinds[kind][key] = plain[name][idx]
                blocks.append(tuple(kinds[k] for k in KINDS))
            stacked.append({"mask": jnp.asarray(masks[c]), "blocks": tuple(blocks)})
        shared = {key: plain[name] for name, key in SHARED_LEAVES.items()}
        return tuple(stacked), shared

    def state(self, plain: Dict[str, jax.Array]):
        """The train step's whole input state: params and zero moments."""
        stacked, shared = self.params(plain)
        opt, shared_opt = self.abstract["opt"], self.abstract["shared_opt"]
        zeros = lambda tree: jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), tree
        )
        state = dict(
            params=stacked,
            shared=shared,
            opt=type(opt)(t=jnp.zeros((), jnp.int32), m=zeros(stacked), v=zeros(stacked)),
            shared_opt=type(shared_opt)(
                t=jnp.zeros((), jnp.int32), m=zeros(shared), v=zeros(shared)
            ),
        )
        self._check(state)
        return state

    def _check(self, state) -> None:
        want = jax.tree_util.tree_structure(self.abstract)
        got = jax.tree_util.tree_structure(state)
        if want != got:
            raise ValueError(f"the program's state is laid out as {want}, not {got}")
        for a, b in zip(jax.tree_util.tree_leaves(self.abstract), jax.tree_util.tree_leaves(state)):
            if a.shape != b.shape or a.dtype != b.dtype:
                raise ValueError(f"program leaf {a.shape} {a.dtype} != {b.shape} {b.dtype}")

    def init_fn(self, config: Dict[str, Any]):
        """jit: key words -> the step's state, made on the mesh as laid out."""
        return jax.jit(
            lambda kw: self.state(plain_weights(kw, config)), out_shardings=self.shardings
        )

    # ------------------------------------------------------------------ #
    # reading the program's state back, per plain leaf and layer
    # ------------------------------------------------------------------ #
    def leaf_norms(self, stacked, shared) -> Dict[str, jax.Array]:
        """Frobenius norm of every plain leaf: (L,) for layer leaves, () else."""
        out = {name: jnp.zeros((self.L,), jnp.float32) for name in LAYER_LEAVES}
        for c in range(self.nc):
            for bi in range(self.g):
                idx = self.layer[c][bi]
                real = idx < self.L
                for name, (kind, key) in LAYER_LEAVES.items():
                    leaf = stacked[c]["blocks"][bi][KINDS.index(kind)][key]
                    x = leaf.astype(jnp.float32)
                    n = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
                    out[name] = out[name].at[idx[real]].set(n[real])
        for name, key in SHARED_LEAVES.items():
            x = shared[key].astype(jnp.float32)
            out[name] = jnp.sqrt(jnp.sum(x * x))
        return out

    def moment_norms_fn(self):
        """jit: state -> per-leaf norms of the first moment."""
        return jax.jit(lambda opt_m, shared_m: self.leaf_norms(opt_m, shared_m))

    def change_norms_fn(self, config: Dict[str, Any]):
        """jit: (params, shared, key words) -> per-leaf norms of the change
        from the seed's weights, which are made again inside."""

        def f(stacked, shared, kw):
            s0, sh0 = self.params(plain_weights(kw, config))
            diff = lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32)
            return self.leaf_norms(
                jax.tree_util.tree_map(diff, stacked, s0),
                jax.tree_util.tree_map(diff, shared, sh0),
            )

        return jax.jit(f)
