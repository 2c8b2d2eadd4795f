"""SPMD ticked pipeline executor (shard_map over the pipe axis).

Runs any :class:`ExecutionPlan` -- 1F1B, ZB-H1/H2, ZB-V, interleaved,
auto-searched -- as one SPMD program:

  * time is quantized into *ticks*; at tick t every stage looks up its op in
    the static ``(p, T)`` tables compiled from the schedule and
    ``lax.switch``es into the F / B / W / idle branch for the op's chunk
    (generic modes) -- or, in the ``specialized`` mode, each tick is traced
    against its host-constant table column: direct branch calls, per-tick
    constant folding, and a steady-state scan superstep (DESIGN.md Sec. 8);
  * activations and activation-gradients cross stages through four
    collective-permute channels (F-up, F-down, B-down, B-up), closed once per
    tick *outside* the switch (pipe-axis collectives must be unconditional
    under SPMD); channels a schedule never uses are pruned at trace time,
    and the specialized mode emits a permute only on (tick, channel) pairs
    where the plan actually communicates, with exact sender/receiver edges;
  * per-stage state lives in slot-addressed buffers whose sizes come from the
    plan's interval analysis: activation/gradient inboxes, residuals (F->B,
    freed when B completes -- the paper's accounting), weight-grad contexts
    (B->W; the byte-minimal M_W context of the compact split, including any
    stacked per-step scan contexts -- DESIGN.md Sec. 7), and the
    head+loss residuals/contexts at the loss position.  When the chunks'
    buffer structures agree (the uniform-group SPMD case), residual and
    W-context pools are shared across chunks, so the per-device footprint is
    the plan's joint cross-chunk peak, not the sum of per-chunk peaks.

SPMD invariant: collectives over the *tensor-parallel* axis may appear inside
switch branches (all ranks of a TP group share the stage index and therefore
the branch); collectives over the *pipe* axis must stay outside.  See
DESIGN.md Sec. 3.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .passes import FBWModule
from .schedules.ir import (
    CHANNEL_BWD_DOWN,
    CHANNEL_BWD_UP,
    CHANNEL_FWD_DOWN,
    CHANNEL_FWD_UP,
    ExecutionPlan,
    N_CHANNELS,
    OpKind,
)

PyTree = Any

__all__ = ["PipelineProgram", "PipelineExecutor", "microbatch_split"]

_CHANNEL_SHIFT = {
    CHANNEL_FWD_UP: +1,
    CHANNEL_FWD_DOWN: -1,
    CHANNEL_BWD_DOWN: -1,
    CHANNEL_BWD_UP: +1,
}


@dataclasses.dataclass
class PipelineProgram:
    """What the model hands the executor.

    ``chunks[c]`` is the FBW module computing chunk ``c``'s layer group on one
    stage (structurally identical across stages; parameters differ).  ``src``
    produces the chunk-0 input from the per-microbatch side inputs (embedding
    or modality-frontend stub); ``sink`` maps the last chunk's output + side
    inputs to the scalar loss (final norm + LM head + CE).  Shared parameters
    (embedding table, head) are replicated along the pipe axis and their
    gradients psum'd over it.
    """

    chunks: Sequence[FBWModule]
    src_fwd: Callable[[PyTree, PyTree], jax.Array]  # (shared, side_mb) -> x
    src_bwd_w: Callable[[PyTree, PyTree, jax.Array], PyTree]  # -> shared grads
    sink: FBWModule  # fwd(shared, y, side_mb) -> loss; auto_fbw-split
    act_shape: Tuple[int, ...]  # (b_mb, s, h) carried between stages
    act_dtype: Any = jnp.float32

    def n_chunks(self) -> int:
        return len(self.chunks)


def microbatch_split(batch: PyTree, m: int) -> PyTree:
    """(G, ...) -> (m, G/m, ...) microbatch axis up front."""
    def split(x):
        g = x.shape[0]
        assert g % m == 0, f"batch {g} not divisible by m={m}"
        return x.reshape((m, g // m) + x.shape[1:])

    return jax.tree_util.tree_map(split, batch)


def _dyn_get(buf: jax.Array, idx: jax.Array) -> jax.Array:
    """buf[(idx, ...)] with a traced index."""
    return jax.lax.dynamic_index_in_dim(buf, idx, 0, keepdims=False)


def _dyn_set(buf: jax.Array, idx: jax.Array, val: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_index_in_dim(buf, val, idx, 0)


def _masked_set(buf, idx, val, active):
    """In-place slot write that keeps the old value when inactive.

    ``active`` may be a Python/numpy bool (the specialized executor bakes
    per-tick constants): True folds to a plain slot write, False to a
    no-op, so statically-dead writes never reach XLA.
    """
    if isinstance(active, (bool, np.bool_)):
        if not active:
            return buf
        return _dyn_set(buf, idx, val.astype(buf.dtype))
    old = _dyn_get(buf, idx)
    act = jnp.asarray(active)
    sel = jnp.where(
        act.reshape((1,) * val.ndim) if val.ndim else act, val, old
    ).astype(buf.dtype)
    return _dyn_set(buf, idx, sel)


def _maybe_cond(pred, true_fn, false_fn, operand):
    """``lax.cond`` that folds at trace time on a host-constant predicate.

    The branch bodies are written once and reused by both executor modes;
    under specialization the per-tick flags arrive as Python bools and the
    untaken side must not be traced at all (it may index buffers that the
    plan proves dead at this tick).
    """
    if isinstance(pred, (bool, np.bool_)):
        return true_fn(operand) if pred else false_fn(operand)
    return jax.lax.cond(pred, true_fn, false_fn, operand)


def _tree_dyn_get(bufs: PyTree, idx) -> PyTree:
    return jax.tree_util.tree_map(lambda b: _dyn_get(b, idx), bufs)


def _tree_dyn_set(bufs: PyTree, idx, vals: PyTree) -> PyTree:
    return jax.tree_util.tree_map(lambda b, v: _dyn_set(b, idx, v.astype(b.dtype)), bufs, vals)


def _zeros_buffer(shape_dtype: jax.ShapeDtypeStruct, slots: int) -> jax.Array:
    return jnp.zeros((slots,) + tuple(shape_dtype.shape), shape_dtype.dtype)


class PipelineExecutor:
    """Compiles (program, plan) into a pipelined grads-and-loss function.

    The returned ``grad_fn(stage_params, shared, batch_side) -> (grads,
    shared_grads, loss)`` is pure and shard_map-compatible: it must run inside
    a shard_map whose ``axis_name == pipe_axis``; ``stage_params`` are this
    stage's (already-local) parameters.
    """

    def __init__(
        self,
        program: PipelineProgram,
        plan: ExecutionPlan,
        pipe_axis: str = "data",
        unroll: bool = False,
        prune_channels: bool = True,
        tp_axis: Optional[str] = None,
        shard_channels: bool = False,
        fuse_wgrad: bool = True,
        tp_size: Optional[int] = None,
        mode: Optional[str] = None,
        steady_scan: bool = True,
    ):
        if program.n_chunks() != plan.n_chunks:
            raise ValueError(
                f"program has {program.n_chunks()} chunks, plan {plan.n_chunks}"
            )
        self.fuse_wgrad = fuse_wgrad
        self.program = program
        self.plan = plan
        self.pipe_axis = pipe_axis
        self.unroll = unroll
        # Compilation mode (DESIGN.md Sec. 8):
        #   "scan"        -- one generic tick body inside lax.scan; every tick
        #                    pays the full switch + all live channels;
        #   "unroll"      -- the generic tick unrolled (legacy unroll=True);
        #   "specialized" -- each tick traced against its host-constant plan
        #                    column: direct branch calls, exact-edge permutes
        #                    only where the plan communicates, and the steady
        #                    window compiled once inside a scan superstep.
        if mode is None:
            mode = "unroll" if unroll else "scan"
        if mode not in ("scan", "unroll", "specialized"):
            raise ValueError(f"unknown executor mode {mode!r}")
        self.mode = mode
        self.steady_scan = steady_scan
        self.channels = (
            plan.used_channels() if prune_channels else tuple(range(N_CHANNELS))
        )
        # Sequence-sharded channels (beyond-paper, EXPERIMENTS.md Perf):
        # every TP rank otherwise permutes a redundant full activation copy
        # over the (slow) pipe links; instead each rank sends its 1/tp seq
        # slice and the consumer all-gathers over the (fast) TP links.
        self.tp_axis = tp_axis
        self.shard_channels = bool(shard_channels and tp_axis is not None)
        # static TP degree hint for *byte accounting only*: the runtime
        # channel shape divides seq by psum(1, tp_axis) at trace time, which
        # abstract sizing cannot see (buffer_bytes / channel_message_bytes)
        self.tp_size = tp_size

    # ------------------------------------------------------------------ #
    def _abstract_state(self, stage_params, shared, side_all):
        """Shape-evaluate chunk/sink residual structures to size the buffers."""
        prog, plan = self.program, self.plan
        side_mb = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), side_all
        )
        act = jax.ShapeDtypeStruct(prog.act_shape, prog.act_dtype)

        res_shapes, wctx_shapes = [], []
        y_shape = act
        for c, mod in enumerate(prog.chunks):
            fwd_out = jax.eval_shape(
                lambda p, x, sd: mod.fwd(p, x, sd), stage_params[c], act, side_mb
            )
            y_shape, res_shape = fwd_out
            res_shapes.append(res_shape)
            dy = act
            bwd_out = jax.eval_shape(
                lambda p, r, g, sd: mod.bwd_x(p, r, g, sd),
                stage_params[c],
                res_shape,
                dy,
                side_mb,
            )
            _, wctx_shape = bwd_out
            wctx_shapes.append(wctx_shape)

        sink_out = jax.eval_shape(
            lambda sh, y, sd: prog.sink.fwd(sh, y, sd), shared, act, side_mb
        )
        loss_shape, sink_res_shape = sink_out
        ones = jax.ShapeDtypeStruct(loss_shape.shape, loss_shape.dtype)
        _, sink_wctx_shape = jax.eval_shape(
            lambda sh, r, g, sd: prog.sink.bwd_x(sh, r, g, sd),
            shared,
            sink_res_shape,
            ones,
            side_mb,
        )
        return res_shapes, wctx_shapes, sink_res_shape, sink_wctx_shape, loss_shape

    @staticmethod
    def _uniform(shapes) -> bool:
        """True when every chunk's buffer pytree has identical structure."""
        sig = [
            (
                jax.tree_util.tree_structure(sh),
                tuple(
                    (tuple(l.shape), jnp.dtype(l.dtype).name)
                    for l in jax.tree_util.tree_leaves(sh)
                ),
            )
            for sh in shapes
        ]
        return all(s == sig[0] for s in sig)

    # ------------------------------------------------------------------ #
    # measured buffer accounting (what the tick executor actually allocates)
    # ------------------------------------------------------------------ #
    @staticmethod
    def _tree_bytes(sh) -> int:
        return int(
            sum(
                int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
                for l in jax.tree_util.tree_leaves(sh)
            )
        )

    def state_shapes(self, stage_params, shared, side_all):
        """Abstract buffer state: per-slot structures + slot counts.

        ``stage_params`` / ``shared`` / ``side_all`` may be real arrays or
        ``ShapeDtypeStruct`` pytrees; nothing is computed.
        """
        plan = self.plan
        res_sh, wctx_sh, sink_sh, sink_wctx_sh, loss_sh = self._abstract_state(
            stage_params, shared, side_all
        )
        share_res = self._uniform(res_sh)
        share_wctx = self._uniform(wctx_sh)
        return dict(
            res=res_sh,
            wctx=wctx_sh,
            sink=sink_sh,
            sink_wctx=sink_wctx_sh,
            loss=loss_sh,
            share_res=share_res,
            share_wctx=share_wctx,
            n_res_slots=(
                (plan.n_res_slots_joint,) if share_res else plan.n_res_slots
            ),
            n_wctx_slots=(
                (plan.n_wctx_slots_joint,) if share_wctx else plan.n_wctx_slots
            ),
            n_sink_slots=plan.n_sink_slots,
            n_sink_wctx_slots=plan.n_sink_wctx_slots,
        )

    def channel_message_bytes(self) -> float:
        """Bytes of one inbox slot (one inter-stage message).

        With ``shard_channels`` each rank carries only its 1/tp seq slice;
        that division is exact when the constructor got the static
        ``tp_size`` hint, otherwise the *unsharded* shape is returned --
        an upper bound, so byte-budget feasibility errs conservative.
        """
        full = int(np.prod(self.program.act_shape)) * jnp.dtype(
            self.program.act_dtype
        ).itemsize
        if self.shard_channels and self.tp_size:
            return float(full) / self.tp_size
        return float(full)

    def buffer_bytes(self, stage_params, shared, side_all):
        """Bytes the executor allocates per device, by buffer family.

        These are the *measured* numbers the analytic byte model is checked
        against (tests/test_measured_memory.py): slot-addressed pools are
        sized by the plan's interval analysis, so total allocation equals the
        peak of live bytes over the run (greedy interval coloring is optimal
        on interval graphs).
        """
        plan = self.plan
        st = self.state_shapes(stage_params, shared, side_all)
        res_sh, wctx_sh = st["res"], st["wctx"]
        res_slot_bytes = [self._tree_bytes(sh) for sh in res_sh]
        wctx_slot_bytes = [self._tree_bytes(sh) for sh in wctx_sh]
        if st["share_res"]:
            res_total = plan.n_res_slots_joint * res_slot_bytes[0]
        else:
            res_total = sum(
                n * b for n, b in zip(plan.n_res_slots, res_slot_bytes)
            )
        if st["share_wctx"]:
            wctx_total = plan.n_wctx_slots_joint * wctx_slot_bytes[0]
        else:
            wctx_total = sum(
                n * b for n, b in zip(plan.n_wctx_slots, wctx_slot_bytes)
            )
        # flat (C, max-slots) inbox buffers: see ExecutionPlan.inbox_slot_total
        inbox_total = plan.inbox_slot_total() * self.channel_message_bytes()
        sink_total = plan.n_sink_slots * self._tree_bytes(st["sink"])
        sink_wctx_total = plan.n_sink_wctx_slots * self._tree_bytes(
            st["sink_wctx"]
        )
        # per-block W-context bytes (one entry per block of each chunk, when
        # the chunk module exposes a per-block context tuple -- ChunkFBW
        # does).  Stacked scan-split contexts are ordinary leaves here; this
        # is the number the recurrent-split acceptance measures.
        wctx_block_bytes = tuple(
            tuple(self._tree_bytes(blk) for blk in sh)
            if isinstance(sh, (tuple, list))
            else (self._tree_bytes(sh),)
            for sh in wctx_sh
        )
        return dict(
            res=float(res_total),
            wctx=float(wctx_total),
            inbox=float(inbox_total),
            sink=float(sink_total),
            sink_wctx=float(sink_wctx_total),
            total=float(
                res_total + wctx_total + inbox_total + sink_total
                + sink_wctx_total
            ),
            res_slot_bytes=tuple(float(b) for b in res_slot_bytes),
            wctx_slot_bytes=tuple(float(b) for b in wctx_slot_bytes),
            wctx_block_bytes=wctx_block_bytes,
        )

    # ------------------------------------------------------------------ #
    def build_grad_fn(self):
        prog, plan = self.program, self.plan
        C = plan.n_chunks
        act_sd = jax.ShapeDtypeStruct(prog.act_shape, prog.act_dtype)

        def grad_fn(stage_params, shared, side_all):
            # -- static residual structures -------------------------------- #
            res_sh, wctx_sh, sink_sh, sink_wctx_sh, loss_sh = (
                self._abstract_state(stage_params, shared, side_all)
            )
            share_res = self._uniform(res_sh)
            share_wctx = self._uniform(wctx_sh)

            # -- stage index (tick tables are gathered per mode below) ------ #
            sidx = jax.lax.axis_index(self.pipe_axis)

            # -- buffers ----------------------------------------------------- #
            S_act = max(plan.n_act_slots)
            S_grad = max(plan.n_grad_slots)
            if self.shard_channels:
                tp_size = jax.lax.psum(1, self.tp_axis)
                assert prog.act_shape[1] % tp_size == 0, (
                    f"seq {prog.act_shape[1]} must divide tp={tp_size} for"
                    " sequence-sharded channels"
                )
                chan_shape = (
                    prog.act_shape[0],
                    prog.act_shape[1] // tp_size,
                ) + prog.act_shape[2:]
            else:
                chan_shape = prog.act_shape
            act_in = jnp.zeros((C, S_act) + chan_shape, prog.act_dtype)
            grad_in = jnp.zeros((C, S_grad) + chan_shape, prog.act_dtype)

            def to_chan(full):
                """Slice this rank's seq shard for the channel payload."""
                if not self.shard_channels:
                    return full
                r = jax.lax.axis_index(self.tp_axis)
                k = chan_shape[1]
                return jax.lax.dynamic_slice_in_dim(full, r * k, k, axis=1)

            def from_chan(slice_):
                """Reassemble the full activation from seq shards."""
                if not self.shard_channels:
                    return slice_
                return jax.lax.all_gather(
                    slice_, self.tp_axis, axis=1, tiled=True
                )
            # Residual / W-context pools.  Shared across chunks (joint slot
            # ids) when every chunk's buffer structure matches; per-chunk
            # pools otherwise.  Residual slots are live [F, B] only: B's true
            # split-VJP leaves nothing for W to rebuild.
            if share_res:
                res_buf = jax.tree_util.tree_map(
                    lambda sd: _zeros_buffer(sd, plan.n_res_slots_joint),
                    res_sh[0],
                )
            else:
                res_buf = [
                    jax.tree_util.tree_map(
                        lambda sd: _zeros_buffer(sd, plan.n_res_slots[c]),
                        res_sh[c],
                    )
                    for c in range(C)
                ]
            if share_wctx:
                wctx_buf = jax.tree_util.tree_map(
                    lambda sd: _zeros_buffer(sd, plan.n_wctx_slots_joint),
                    wctx_sh[0],
                )
            else:
                wctx_buf = [
                    jax.tree_util.tree_map(
                        lambda sd: _zeros_buffer(sd, plan.n_wctx_slots[c]),
                        wctx_sh[c],
                    )
                    for c in range(C)
                ]
            sink_buf = jax.tree_util.tree_map(
                lambda sd: _zeros_buffer(sd, plan.n_sink_slots), sink_sh
            )
            sink_wctx_buf = jax.tree_util.tree_map(
                lambda sd: _zeros_buffer(sd, plan.n_sink_wctx_slots),
                sink_wctx_sh,
            )

            def pool_get(buf, shared_pool, c, idx):
                return _tree_dyn_get(buf if shared_pool else buf[c], idx)

            def pool_set(buf, shared_pool, c, idx, vals):
                if shared_pool:
                    return _tree_dyn_set(buf, idx, vals)
                lst = list(buf)
                lst[c] = _tree_dyn_set(lst[c], idx, vals)
                return lst
            acc_dt = lambda leaf: jnp.promote_types(leaf.dtype, jnp.float32)
            grad_acc = jax.tree_util.tree_map(
                lambda pleaf: jnp.zeros(pleaf.shape, acc_dt(pleaf)), stage_params
            )
            shared_acc = jax.tree_util.tree_map(
                lambda pleaf: jnp.zeros(pleaf.shape, acc_dt(pleaf)), shared
            )
            loss_acc = jnp.zeros((), jnp.promote_types(loss_sh.dtype, jnp.float32))

            state0 = dict(
                act_in=act_in,
                grad_in=grad_in,
                res=res_buf,
                wctx=wctx_buf,
                sink=sink_buf,
                sink_wctx=sink_wctx_buf,
                grad_acc=grad_acc,
                shared_acc=shared_acc,
                loss=loss_acc,
            )

            zero_act = jnp.zeros(prog.act_shape, prog.act_dtype)

            # -- branch bodies ---------------------------------------------- #
            def side_at(mb):
                return jax.tree_util.tree_map(
                    lambda a: _dyn_get(a, mb), side_all
                )

            def f_branch(c):
                @jax.named_scope("F")
                def body(state, t):
                    side_mb = side_at(t["mb"])
                    x_inbox = from_chan(_dyn_get(state["act_in"][c], t["in_slot"]))

                    def from_src(_):
                        return prog.src_fwd(shared, side_mb).astype(prog.act_dtype)

                    x = _maybe_cond(
                        t["is_src"], from_src, lambda _: x_inbox, None
                    )
                    y, res = prog.chunks[c].fwd(stage_params[c], x, side_mb)
                    state = dict(state)
                    state["res"] = pool_set(
                        state["res"], share_res, c, t["res_slot"], res
                    )

                    def with_loss(st):
                        with jax.named_scope("head_ce"):
                            loss, sres = prog.sink.fwd(shared, y, side_mb)
                        st = dict(st)
                        st["sink"] = jax.tree_util.tree_map(
                            lambda b, v: _masked_set(b, t["sink_slot"], v, True),
                            st["sink"],
                            sres,
                        )
                        st["loss"] = st["loss"] + loss.astype(st["loss"].dtype)
                        return st

                    state = _maybe_cond(
                        t["is_loss"], with_loss, lambda st: st, state
                    )
                    return state, y.astype(prog.act_dtype)

                return body

            def b_branch(c):
                @jax.named_scope("B")
                def body(state, t):
                    side_mb = side_at(t["mb"])
                    res = pool_get(state["res"], share_res, c, t["res_slot"])
                    dy_inbox = from_chan(
                        _dyn_get(state["grad_in"][c], t["in_slot"])
                    )
                    state = dict(state)

                    if c == C - 1:
                        def from_sink(_):
                            sres = _tree_dyn_get(state["sink"], t["sink_slot"])
                            ones = jnp.ones(loss_sh.shape, loss_sh.dtype)
                            with jax.named_scope("head_ce"):
                                dy_s, swctx = prog.sink.bwd_x(
                                    shared, sres, ones, side_mb
                                )
                            return dy_s.astype(prog.act_dtype), swctx

                        def from_inbox(_):
                            zeros = jax.tree_util.tree_map(
                                lambda sd: jnp.zeros(sd.shape, sd.dtype),
                                sink_wctx_sh,
                            )
                            return dy_inbox, zeros

                        dy, swctx_val = _maybe_cond(
                            t["is_loss"], from_sink, from_inbox, None
                        )
                        state["sink_wctx"] = jax.tree_util.tree_map(
                            lambda b, v: _masked_set(
                                b, t["sink_wctx_slot"], v, t["is_loss"]
                            ),
                            state["sink_wctx"],
                            swctx_val,
                        )
                    else:
                        dy = dy_inbox

                    # True input-gradient VJP: emits the compact M_W context
                    # (the byte-minimal cut; for split recurrences a stacked
                    # per-step context); the residual slot is dead after
                    # this tick and the interval analysis reuses it.
                    dx, wctx = prog.chunks[c].bwd_x(
                        stage_params[c], res, dy, side_mb
                    )
                    state["wctx"] = pool_set(
                        state["wctx"], share_wctx, c, t["wctx_slot"], wctx
                    )

                    if c == 0:
                        def embed_grads(st):
                            g = prog.src_bwd_w(shared, side_mb, dx)
                            st = dict(st)
                            st["shared_acc"] = jax.tree_util.tree_map(
                                lambda a, b: a + b.astype(a.dtype),
                                st["shared_acc"],
                                g,
                            )
                            return st

                        state = _maybe_cond(
                            t["is_last_b"], embed_grads, lambda st: st, state
                        )
                    return state, dx.astype(prog.act_dtype)

                return body

            def w_branch(c):
                @jax.named_scope("W")
                def body(state, t):
                    side_mb = side_at(t["mb"])
                    # W consumes only the M_W context -- no residuals, no
                    # pullback rebuild.  Terminal dW = a^T @ g products are
                    # fused into the accumulator via kernels/wgrad_accum.
                    wctx = pool_get(state["wctx"], share_wctx, c, t["wctx_slot"])
                    state = dict(state)
                    acc = list(state["grad_acc"])
                    if self.fuse_wgrad:
                        acc[c] = prog.chunks[c].bwd_w(
                            stage_params[c], wctx, side_mb, acc=acc[c]
                        )
                    else:
                        g = prog.chunks[c].bwd_w(stage_params[c], wctx, side_mb)
                        acc[c] = jax.tree_util.tree_map(
                            lambda a, b: a + b.astype(a.dtype), acc[c], g
                        )
                    state["grad_acc"] = type(state["grad_acc"])(acc)

                    if c == C - 1:
                        def sink_grads(st):
                            swctx = _tree_dyn_get(
                                st["sink_wctx"], t["sink_wctx_slot"]
                            )
                            with jax.named_scope("head_ce"):
                                sg = prog.sink.bwd_w(shared, swctx, side_mb)
                            st = dict(st)
                            st["shared_acc"] = jax.tree_util.tree_map(
                                lambda a, b: a + b.astype(a.dtype),
                                st["shared_acc"],
                                sg,
                            )
                            return st

                        state = _maybe_cond(
                            t["is_loss"], sink_grads, lambda st: st, state
                        )
                    return state, zero_act

                return body

            def idle_branch(state, t):
                return state, zero_act

            branches = [idle_branch]
            for c in range(C):
                branches.append(f_branch(c))
            for c in range(C):
                branches.append(b_branch(c))
            for c in range(C):
                branches.append(w_branch(c))

            def branch_index(kind, chunk):
                # idle=0; F: 1+c; B: 1+C+c; W: 1+2C+c
                base = jnp.where(
                    kind == int(OpKind.F),
                    1,
                    jnp.where(kind == int(OpKind.B), 1 + C, 1 + 2 * C),
                )
                return jnp.where(kind == int(OpKind.IDLE), 0, base + chunk)

            # -- one tick ----------------------------------------------------- #
            def tick(state, t):
                idx = branch_index(t["kind"], t["chunk"])
                state, send_full = jax.lax.switch(idx, branches, state, t)
                send_val = to_chan(send_full)
                zero_chan = jnp.zeros(chan_shape, prog.act_dtype)

                # local (same-stage) deposit: chunk turns in V placement
                is_local_act = t["send_local"] & ~t["local_is_grad"]
                is_local_grad = t["send_local"] & t["local_is_grad"]
                flat_a = state["act_in"].reshape((-1,) + chan_shape)
                flat_g = state["grad_in"].reshape((-1,) + chan_shape)
                a_idx = t["local_chunk"] * S_act + t["local_slot"]
                g_idx = t["local_chunk"] * S_grad + t["local_slot"]
                flat_a = _masked_set(flat_a, a_idx, send_val, is_local_act)
                flat_g = _masked_set(flat_g, g_idx, send_val, is_local_grad)

                # channel sends: one collective-permute per live channel
                for d in self.channels:
                    payload = jnp.where(
                        t["send_channel"] == d, send_val, zero_chan
                    )
                    shift = _CHANNEL_SHIFT[d]
                    p = plan.p
                    perm = [(i, (i + shift) % p) for i in range(p)]
                    with jax.named_scope("send"):
                        got = jax.lax.ppermute(payload, self.pipe_axis, perm)
                    is_act_chan = d in (CHANNEL_FWD_UP, CHANNEL_FWD_DOWN)
                    valid = t["recv_valid"][d]
                    ridx = t["recv_chunk"][d] * (
                        S_act if is_act_chan else S_grad
                    ) + t["recv_slot"][d]
                    if is_act_chan:
                        flat_a = _masked_set(flat_a, ridx, got, valid)
                    else:
                        flat_g = _masked_set(flat_g, ridx, got, valid)

                state = dict(state)
                state["act_in"] = flat_a.reshape((C, S_act) + chan_shape)
                state["grad_in"] = flat_g.reshape((C, S_grad) + chan_shape)
                return state, None

            # grad_acc over chunks must be a tuple for the _tree ops
            state0["grad_acc"] = tuple(
                jax.tree_util.tree_map(
                    lambda pleaf: jnp.zeros(pleaf.shape, acc_dt(pleaf)), sp
                )
                for sp in stage_params
            )

            if self.mode == "specialized":
                state = self._run_specialized(
                    state0,
                    branches,
                    sidx,
                    share_res,
                    share_wctx,
                    S_act,
                    S_grad,
                    chan_shape,
                    to_chan,
                    zero_act,
                )
            elif self.mode == "unroll":
                xs = self._tick_rows(sidx, share_res, share_wctx)
                state = state0
                for t_i in range(plan.n_ticks):
                    t = jax.tree_util.tree_map(lambda a: a[t_i], xs)
                    state, _ = tick(state, t)
            else:
                xs = self._tick_rows(sidx, share_res, share_wctx)
                state, _ = jax.lax.scan(
                    tick, state0, xs, length=plan.n_ticks
                )

            grads = state["grad_acc"]
            with jax.named_scope("opt"), jax.named_scope("sync"):
                shared_grads = jax.lax.psum(state["shared_acc"], self.pipe_axis)
                loss = jax.lax.psum(state["loss"], self.pipe_axis)
            return grads, shared_grads, loss

        return grad_fn

    # ------------------------------------------------------------------ #
    # generic modes: per-stage (T,)-rows of the tick tables
    # ------------------------------------------------------------------ #
    def _tick_rows(self, sidx, share_res, share_wctx):
        plan = self.plan

        def row(tab):
            return jnp.asarray(tab)[sidx]

        return dict(
            kind=row(plan.op_kind),
            chunk=row(plan.op_chunk),
            mb=row(plan.op_mb),
            in_slot=row(plan.op_in_slot),
            res_slot=row(
                plan.op_res_slot_joint if share_res else plan.op_res_slot
            ),
            wctx_slot=row(
                plan.op_wctx_slot_joint if share_wctx else plan.op_wctx_slot
            ),
            sink_slot=row(plan.op_sink_slot),
            sink_wctx_slot=row(plan.op_sink_wctx_slot),
            is_src=row(plan.op_is_src),
            is_loss=row(plan.op_is_loss),
            is_last_b=row(plan.op_is_last_b),
            send_channel=row(plan.send_channel),
            send_local=row(plan.send_local),
            local_chunk=row(plan.local_chunk),
            local_slot=row(plan.local_slot),
            local_is_grad=row(plan.local_is_grad),
            recv_valid=row(plan.recv_valid),
            recv_chunk=row(plan.recv_chunk),
            recv_slot=row(plan.recv_slot),
        )

    # ------------------------------------------------------------------ #
    # specialized mode: trace each tick against its host-constant column
    # ------------------------------------------------------------------ #
    def _run_specialized(
        self,
        state0,
        branches,
        sidx,
        share_res,
        share_wctx,
        S_act,
        S_grad,
        chan_shape,
        to_chan,
        zero_act,
    ):
        """Unroll the tick stream with per-tick Python constants.

        Per tick: the (kind, chunk) column selects a *direct* branch call
        (or a 2-way ``cond`` / minimal ``switch`` when stages disagree);
        a ``ppermute`` is emitted only for (tick, channel) pairs where the
        plan actually sends, with the exact (sender, receiver) edge list;
        slot indices uniform across the participating stages become static
        update indices.  The steady window (``plan.steady_window()``)
        compiles once inside a ``lax.scan`` superstep with the microbatch
        advanced by ``mb_delta`` per period, bounding trace size at large
        ``p*m``.  Arithmetic, op order, and accumulation order are
        identical to the generic modes, so results are bit-identical.
        """
        plan = self.plan
        C = plan.n_chunks
        p = plan.p

        def pscal(vec, mask=None):
            """(p,) column -> per-stage scalar.  Host columns fold to a
            Python constant when the participating stages agree (static
            slot indices); traced columns (scanned steady-state inputs)
            and disagreeing stages become a tiny gather by stage index."""
            if isinstance(vec, jax.Array):
                return vec[sidx]
            v = np.asarray(vec)
            sel = v if mask is None else v[mask]
            if sel.size and (sel == sel.flat[0]).all():
                return sel.flat[0].item()
            return jnp.asarray(v)[sidx]

        def _pred(mask):
            return True if mask.all() else jnp.asarray(mask)[sidx]

        def make_t(col, mask):
            return dict(
                mb=pscal(col["op_mb"], mask),
                in_slot=pscal(col["op_in_slot"], mask),
                res_slot=pscal(
                    col["op_res_slot_joint"]
                    if share_res
                    else col["op_res_slot"],
                    mask,
                ),
                wctx_slot=pscal(
                    col["op_wctx_slot_joint"]
                    if share_wctx
                    else col["op_wctx_slot"],
                    mask,
                ),
                sink_slot=pscal(col["op_sink_slot"], mask),
                sink_wctx_slot=pscal(col["op_sink_wctx_slot"], mask),
                is_src=pscal(col["op_is_src"], mask),
                is_loss=pscal(col["op_is_loss"], mask),
                is_last_b=pscal(col["op_is_last_b"], mask),
            )

        def branch_vec(col):
            kind, chunk = col["op_kind"], col["op_chunk"]
            base = np.where(
                kind == int(OpKind.F),
                1,
                np.where(kind == int(OpKind.B), 1 + C, 1 + 2 * C),
            )
            return np.where(kind == int(OpKind.IDLE), 0, base + chunk)

        def spec_tick(state, col):
            bidx = branch_vec(col)
            used = sorted(set(bidx.tolist()))

            def wrap(u):
                if u == 0:
                    return lambda st: (st, zero_act)
                tu = make_t(col, bidx == u)
                return lambda st: branches[u](st, tu)

            if used == [0]:
                send = zero_act
            elif len(used) == 1:
                state, send = wrap(used[0])(state)
            elif len(used) == 2:
                pred = jnp.asarray(bidx == used[1])[sidx]
                state, send = jax.lax.cond(
                    pred, wrap(used[1]), wrap(used[0]), state
                )
            else:
                lut = np.searchsorted(used, bidx)
                state, send = jax.lax.switch(
                    jnp.asarray(lut)[sidx], [wrap(u) for u in used], state
                )

            # -- communication: exactly what the plan does at this tick --- #
            live = [
                d for d in self.channels if (col["send_channel"] == d).any()
            ]
            any_local = bool(col["send_local"].any())
            if not live and not any_local:
                return state
            send_val = to_chan(send)
            flat_a = state["act_in"].reshape((-1,) + chan_shape)
            flat_g = state["grad_in"].reshape((-1,) + chan_shape)
            if any_local:
                la = col["send_local"] & ~col["local_is_grad"]
                lg = col["send_local"] & col["local_is_grad"]
                if la.any():
                    idx = col["local_chunk"] * S_act + col["local_slot"]
                    flat_a = _masked_set(
                        flat_a, pscal(idx, la), send_val, _pred(la)
                    )
                if lg.any():
                    idx = col["local_chunk"] * S_grad + col["local_slot"]
                    flat_g = _masked_set(
                        flat_g, pscal(idx, lg), send_val, _pred(lg)
                    )
            for d in live:
                shift = _CHANNEL_SHIFT[d]
                senders = np.nonzero(col["send_channel"] == d)[0]
                edges = [(int(s), int((s + shift) % p)) for s in senders]
                with jax.named_scope("send"):
                    got = jax.lax.ppermute(send_val, self.pipe_axis, edges)
                valid = col["recv_valid"][:, d]
                is_act_chan = d in (CHANNEL_FWD_UP, CHANNEL_FWD_DOWN)
                stride = S_act if is_act_chan else S_grad
                ridx = col["recv_chunk"][:, d] * stride + col["recv_slot"][:, d]
                if is_act_chan:
                    flat_a = _masked_set(
                        flat_a, pscal(ridx, valid), got, _pred(valid)
                    )
                else:
                    flat_g = _masked_set(
                        flat_g, pscal(ridx, valid), got, _pred(valid)
                    )
            state = dict(state)
            state["act_in"] = flat_a.reshape((C, S_act) + chan_shape)
            state["grad_in"] = flat_g.reshape((C, S_grad) + chan_shape)
            return state

        cols = [plan.tick_column(t) for t in range(plan.n_ticks)]
        sw = plan.steady_window() if self.steady_scan else None
        state = state0
        if sw is not None and sw.repeats >= 2:
            for t_i in range(sw.start):
                state = spec_tick(state, cols[t_i])

            # Split each tick-in-period's tables into host constants
            # (identical in every period -- all structural tables are, by
            # the window's definition, and slot tables often too) and
            # per-period scanned inputs (cycling slot ids, microbatch ids).
            const_cols: List[Dict[str, Any]] = []
            var_cols: List[Dict[str, jax.Array]] = []
            for i in range(sw.period):
                ticks = [sw.start + i + j * sw.period for j in range(sw.repeats)]
                cc: Dict[str, Any] = {}
                vv: Dict[str, jax.Array] = {}
                for name in ExecutionPlan._TICK_TABLES:
                    stack = np.stack(
                        [getattr(plan, name)[:, t] for t in ticks]
                    )
                    if (stack == stack[0]).all():
                        cc[name] = stack[0]
                    else:
                        vv[name] = jnp.asarray(stack)
                const_cols.append(cc)
                var_cols.append(vv)

            def superstep(st, xs_i):
                for i in range(sw.period):
                    col = dict(const_cols[i])
                    col.update(xs_i[i])
                    st = spec_tick(st, col)
                return st, None

            state, _ = jax.lax.scan(superstep, state, var_cols)
            tail = range(sw.stop, plan.n_ticks)
        else:
            tail = range(plan.n_ticks)
        for t_i in tail:
            state = spec_tick(state, cols[t_i])
        return state
