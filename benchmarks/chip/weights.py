"""Weights from the seed, in the plain layout and in the program's.

The benchmark makes the weights; neither the program nor the reference
does.  ``plain_weights`` draws them in one layout that the reference reads
directly: the embedding, the head and the final norm, and each tensor of a
layer stacked on a leading axis over the layers that have it.  What a layer
holds is its architecture's (``archs/<architecture>.py`` ``layer_leaves``;
the block's kinds are its leaves' kinds, in their order).
``ProgramLayout`` places the same tensors where the program's train step
takes them (stage-stacked chunks of blocks, shared embedding and head,
zero AdamW moments) and reads the program's state back per layer, so that
the check can compare each layer of each stage with the reference.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SHARED_LEAVES = {"embed": "embed", "head": "head", "final_norm": "final_ln"}


class Leaf(NamedTuple):
    """One tensor of a layer, as an architecture's module states it: where
    the program keeps it (its block's kind and the key there; the block's
    kinds come in the order its leaves first name them), its shape in one
    layer, and its initial value, normal(0, std), or zero where ``std`` is
    None (a norm gain, stored as an offset from 1)."""

    kind: str
    key: str
    shape: Tuple[int, ...]
    std: Optional[float] = None


def layer_rows(arch, config: Dict[str, Any]) -> Dict[str, Dict[int, int]]:
    """Plain layer leaf -> {layer index: its row in the stacked tensor}; a
    leaf is stacked over the layers that have it, in order."""
    rows: Dict[str, Dict[int, int]] = {}
    for li in range(config["num_hidden_layers"]):
        for name in arch.layer_leaves(config, li):
            r = rows.setdefault(name, {})
            r[li] = len(r)
    return rows


def _plain_leaves(arch, config: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], Optional[float]]]:
    """Every plain tensor: name -> (stacked shape, std or None for zero)."""
    h, V, std = config["hidden_size"], config["vocab_size"], config["initializer_range"]
    out = {"embed": ((V, h), std), "head": ((h, V), std), "final_norm": ((h,), None)}
    for name, layers in layer_rows(arch, config).items():
        leaf = arch.layer_leaves(config, next(iter(layers)))[name]
        out[name] = ((len(layers),) + tuple(leaf.shape), leaf.std)
    return out


def plain_shapes(arch, config: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    return {name: shape for name, (shape, _) in _plain_leaves(arch, config).items()}


def plain_weights(arch, key_words, config: Dict[str, Any]) -> Dict[str, jax.Array]:
    """Every tensor, in the configuration's dtype, each drawn from the key
    folded with its place among the sorted names.  Traceable; the key is
    an argument, so one compiled program serves every seed."""
    dt = jnp.dtype(config["torch_dtype"])
    key = jax.random.wrap_key_data(key_words.astype(jnp.uint32))
    out = {}
    for i, (name, (shape, std)) in enumerate(sorted(_plain_leaves(arch, config).items())):
        if std is None:
            out[name] = jnp.zeros(shape, dt)
        else:
            k = jax.random.fold_in(key, i)
            out[name] = (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)
    return out


class ProgramLayout:
    """Where each layer of the plain layout sits in the program's state."""

    def __init__(self, arch, config: Dict[str, Any], setup):
        self.arch, self.config = arch, config
        self.L = config["num_hidden_layers"]
        self.rows = layer_rows(arch, config)
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
        # block[c][bi] -> (kinds, leaves) of that block, alike on every stage
        self.block = [[self._block(c, bi) for bi in range(self.g)] for c in range(nc)]
        self.abstract = jax.eval_shape(setup.init_state, 0)
        self.shardings = setup.state_shardings

    def _block(self, c: int, bi: int):
        idx = np.minimum(self.layer[c][bi], self.L - 1)  # padding is masked
        forms = [self.arch.layer_leaves(self.config, int(li)) for li in idx]
        if any(f != forms[0] for f in forms[1:]):
            raise ValueError(f"block {bi} of chunk {c} stacks layers {idx.tolist()} of different forms")
        leaves = forms[0]
        return tuple(dict.fromkeys(leaf.kind for leaf in leaves.values())), leaves

    def _rows(self, name: str, layers) -> np.ndarray:
        return np.asarray([self.rows[name][int(li)] for li in layers], np.int64)

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
                layers = np.minimum(self.layer[c][bi], self.L - 1)  # padding is masked
                kinds, leaves = self.block[c][bi]
                parts = {k: {} for k in kinds}
                for name, leaf in leaves.items():
                    parts[leaf.kind][leaf.key] = plain[name][self._rows(name, layers)]
                blocks.append(tuple(parts[k] for k in kinds))
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

    def init_fn(self):
        """jit: key words -> the step's state, made on the mesh as laid out."""
        return jax.jit(
            lambda kw: self.state(plain_weights(self.arch, kw, self.config)),
            out_shardings=self.shardings,
        )

    # ------------------------------------------------------------------ #
    # reading the program's state back, per plain leaf and layer
    # ------------------------------------------------------------------ #
    def leaf_norms(self, stacked, shared) -> Dict[str, jax.Array]:
        """Frobenius norm of every plain leaf: one a layer that has it for
        layer leaves, () else."""
        out = {name: jnp.zeros((len(rows),), jnp.float32) for name, rows in self.rows.items()}
        for c in range(self.nc):
            for bi in range(self.g):
                idx = self.layer[c][bi]
                real = idx < self.L
                kinds, leaves = self.block[c][bi]
                for name, leaf in leaves.items():
                    x = stacked[c]["blocks"][bi][kinds.index(leaf.kind)][leaf.key].astype(jnp.float32)
                    n = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
                    out[name] = out[name].at[self._rows(name, idx[real])].set(n[real])
        for name, key in SHARED_LEAVES.items():
            x = shared[key].astype(jnp.float32)
            out[name] = jnp.sqrt(jnp.sum(x * x))
        return out

    def moment_norms_fn(self):
        """jit: state -> per-leaf norms of the first moment."""
        return jax.jit(lambda opt_m, shared_m: self.leaf_norms(opt_m, shared_m))

    def change_norms_fn(self):
        """jit: (params, shared, key words) -> per-leaf norms of the change
        from the seed's weights, which are made again inside."""

        def f(stacked, shared, kw):
            s0, sh0 = self.params(plain_weights(self.arch, kw, self.config))
            diff = lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32)
            return self.leaf_norms(
                jax.tree_util.tree_map(diff, stacked, s0),
                jax.tree_util.tree_map(diff, shared, sh0),
            )

        return jax.jit(f)
