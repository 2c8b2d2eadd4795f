"""Full train/serve step builders: pipeline executor + DP + post-validated
optimizer under one shard_map.  Shared by train.py, dryrun.py and tests."""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from ..core.executor import PipelineExecutor
from ..core.infer_executor import InferExecutor, compile_infer_plan
from ..core.schedules.ir import ExecutionPlan, Placement
from ..models.lm import ArchConfig, RunSpec, build_program
from ..models.serve import build_serve_program
from ..optim import adamw, postval
from .mesh import AxisBinding
from .sharding_rules import shared_param_specs, stacked_param_specs

PyTree = Any

__all__ = ["TrainStepConfig", "build_train_step", "build_serve_step", "param_specs"]


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    adamw: adamw.AdamWConfig = dataclasses.field(default_factory=adamw.AdamWConfig)
    postval_mode: str = "within_step"  # "within_step" | "sync" (baseline)
    grad_compress: str = "none"  # "none" | "bf16" | "int8" (dp all-reduce)
    unroll: bool = False
    prune_channels: bool = True
    shard_channels: bool = False  # seq-shard pipe sends over tp (Perf log)
    # executor compilation mode (DESIGN.md Sec. 8): "scan" (generic tick in
    # lax.scan), "unroll" (generic tick unrolled), or "specialized"
    # (trace-time specialization against the static plan: direct branch
    # calls, exact-edge permutes, steady-state scan superstep).  None keeps
    # the legacy `unroll` bool semantics.
    executor_mode: Optional[str] = None
    # donate params/opt state to the jitted step (they are consumed and
    # re-emitted every step, so aliasing them halves the peak param+moment
    # traffic); callers that re-read the input arrays after stepping must
    # opt out.
    donate: bool = True


def param_specs(stacked, shared, binding: AxisBinding):
    """Per-leaf PartitionSpecs: stage axis over pipe + Megatron TP dims
    (launch/sharding_rules.py); shared params vocab/tp-sharded."""
    stacked_spec = stacked_param_specs(stacked, binding.pipe, binding.tp)
    shared_spec = shared_param_specs(shared, binding.tp)
    return stacked_spec, shared_spec


def _freeze_filter(tree, path_key="mask"):
    """Bool tree: True = frozen (structural masks are not trainable)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        frozen = any(
            getattr(k, "key", None) == path_key for k in path
        )
        out.append(frozen)
    return jax.tree_util.tree_unflatten(treedef, out)


def build_train_step(
    cfg: ArchConfig,
    spec: RunSpec,
    plan: ExecutionPlan,
    placement: Placement,
    mesh,
    binding: AxisBinding,
    tcfg: Optional[TrainStepConfig] = None,
):
    """Returns (step_fn, in_specs, out_specs).

    step_fn(stacked_params, shared, opt_state, shared_opt, side) ->
      (stacked_params, shared, opt_state, shared_opt, metrics)
    """
    tcfg = tcfg or TrainStepConfig()
    program = build_program(cfg, spec, placement, platform=mesh.devices.flat[0].platform)
    execu = PipelineExecutor(
        program,
        plan,
        pipe_axis=binding.pipe,
        unroll=tcfg.unroll,
        prune_channels=tcfg.prune_channels,
        tp_axis=binding.tp,
        shard_channels=tcfg.shard_channels,
        tp_size=binding.sizes(mesh)[1],
        mode=tcfg.executor_mode,
    )
    grad_fn = execu.build_grad_fn()
    p, tp, dp = binding.sizes(mesh)
    acfg = tcfg.adamw

    def body(stacked, shared, opt_state, shared_opt, side):
        unstack = lambda tree: jax.tree_util.tree_map(lambda a: a[0], tree)
        local = tuple(unstack(sp) for sp in stacked)
        opt_state = adamw.AdamWState(
            t=opt_state.t,
            m=tuple(unstack(x) for x in opt_state.m),
            v=tuple(unstack(x) for x in opt_state.v),
        )
        grads, shared_grads, loss = grad_fn(local, shared, side)
        return update(local, shared, opt_state, shared_opt, grads, shared_grads, loss)

    @jax.named_scope("opt")
    def update(local, shared, opt_state, shared_opt, grads, shared_grads, loss):
        """The optimizer tail: dp reduction, freezing, gradient statistics,
        AdamW and post-validation.  Its reductions over the pipe sit under
        ``sync`` as well, where a stage waits for the slowest one."""
        if binding.dp is not None:
            if tcfg.grad_compress != "none":
                from ..optim.compress import compressed_psum

                grads, _ = compressed_psum(grads, binding.dp, tcfg.grad_compress)
                shared_grads, _ = compressed_psum(
                    shared_grads, binding.dp, tcfg.grad_compress
                )
            else:
                grads = jax.lax.psum(grads, binding.dp)
                shared_grads = jax.lax.psum(shared_grads, binding.dp)
            loss = jax.lax.psum(loss, binding.dp)
            scale = 1.0 / dp
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            shared_grads = jax.tree_util.tree_map(
                lambda g: g * scale, shared_grads
            )
            loss = loss * scale

        # freeze structural masks
        frozen = _freeze_filter(local)
        grads = jax.tree_util.tree_map(
            lambda g, f: jnp.zeros_like(g) if f else g, grads, frozen
        )

        # gradient statistics: shared params counted on stage 0 only
        sidx = jax.lax.axis_index(binding.pipe)
        stats_local = postval.local_stats(grads)
        stats_shared = postval.local_stats(shared_grads)
        on0 = (sidx == 0).astype(jnp.float32)
        stats = postval.GradStats(
            stats_local.sumsq + on0 * stats_shared.sumsq,
            stats_local.nonfinite | ((on0 > 0) & stats_shared.nonfinite),
        )

        both_params = (local, shared)
        both_grads = (grads, shared_grads)
        state = adamw.AdamWState(
            t=opt_state.t,
            m=(opt_state.m, shared_opt.m),
            v=(opt_state.v, shared_opt.v),
        )

        if tcfg.postval_mode == "sync":
            # baseline: blocking global reduction before the step
            with jax.named_scope("sync"):
                g_stats = postval.GradStats(
                    jax.lax.psum(stats.sumsq, binding.pipe),
                    jax.lax.psum(
                        stats.nonfinite.astype(jnp.float32), binding.pipe
                    )
                    > 0.5,
                )
            new_params, new_state = postval.sync_step(
                both_params, state, both_grads, acfg, g_stats
            )
            amended = jnp.zeros((), bool)
        else:
            with jax.named_scope("sync"):
                partial_s, full_s = postval.pipe_prefix_stats(stats, binding.pipe)
            p1, s1, dec = postval.optimistic_step(
                both_params, state, both_grads, partial_s, acfg
            )
            new_params, new_state, amended = postval.validate_and_fix(
                p1, s1, both_grads, dec, full_s, acfg
            )

        new_local, new_shared = new_params
        restack = lambda tree: jax.tree_util.tree_map(lambda a: a[None], tree)
        new_opt = adamw.AdamWState(
            t=new_state.t,
            m=tuple(restack(x) for x in new_state.m[0]),
            v=tuple(restack(x) for x in new_state.v[0]),
        )
        new_shared_opt = adamw.AdamWState(
            t=new_state.t, m=new_state.m[1], v=new_state.v[1]
        )
        # shared params must stay replicated over pipe: they already are
        # (identical math on every stage).
        new_stacked = tuple(
            jax.tree_util.tree_map(lambda a: a[None], sp) for sp in new_local
        )
        with jax.named_scope("sync"):
            grad_norm = jnp.sqrt(jax.lax.psum(stats.sumsq, binding.pipe))
        metrics = {"loss": loss, "grad_norm": grad_norm, "amended": amended}
        return new_stacked, new_shared, new_opt, new_shared_opt, metrics

    stacked_sdt, shared_sdt = _abstract_params(cfg, spec, placement)
    stacked_spec, shared_spec = param_specs(stacked_sdt, shared_sdt, binding)
    opt_spec = adamw.AdamWState(
        t=P(), m=stacked_spec, v=stacked_spec
    )
    shared_opt_spec = adamw.AdamWState(t=P(), m=shared_spec, v=shared_spec)
    side_spec = P(binding.dp) if binding.dp else P()
    metrics_spec = {"loss": P(), "grad_norm": P(), "amended": P()}

    in_specs = (stacked_spec, shared_spec, opt_spec, shared_opt_spec, side_spec)
    out_specs = (stacked_spec, shared_spec, opt_spec, shared_opt_spec, metrics_spec)

    def _side_tree_spec(side):
        return jax.tree_util.tree_map(lambda _: side_spec, side)

    def make(side_example):
        fn = shard_map(
            body,
            mesh=mesh,
            in_specs=(
                stacked_spec,
                shared_spec,
                opt_spec,
                shared_opt_spec,
                _side_tree_spec(side_example),
            ),
            out_specs=out_specs,
            check_vma=False,
        )
        # params/opt moments are pure pass-through state: donating them lets
        # XLA update in place instead of double-buffering every leaf.
        donate = (0, 1, 2, 3) if tcfg.donate else ()
        return jax.jit(fn, donate_argnums=donate)

    return make, (in_specs, out_specs)


def _abstract_params(cfg, spec, placement):
    from ..models.lm import init_params

    return jax.eval_shape(lambda: init_params(cfg, spec, placement))


def build_serve_step(
    cfg: ArchConfig,
    spec: RunSpec,
    placement: Placement,
    mesh,
    binding: AxisBinding,
    mode: str,
    cache_len: int,
    donate: bool = True,
):
    """Returns (make(side, caches) -> jitted step, program, cache_init).

    ``donate`` aliases the KV caches into the step (they are consumed and
    re-emitted every call), halving the steady-state cache footprint.
    """
    program, cache_init, cache_pspecs = build_serve_program(cfg, spec, placement, mode)
    plan = compile_infer_plan(placement, spec.m)
    execu = InferExecutor(program, plan, pipe_axis=binding.pipe)
    step = execu.build_step_fn()
    pos = cache_len - 1 if mode == "decode" else 0

    def body(stacked, shared, side, caches):
        local = tuple(jax.tree_util.tree_map(lambda a: a[0], sp) for sp in stacked)
        local_caches = [
            jax.tree_util.tree_map(lambda a: a[0], c) for c in caches
        ]
        out, newc = step(local, shared, side, local_caches, pos)
        newc = [jax.tree_util.tree_map(lambda a: a[None], c) for c in newc]
        return out, newc

    def make(stacked_sdt, shared_sdt, side_example, caches_sdt):
        stacked_spec, shared_spec = param_specs(stacked_sdt, shared_sdt, binding)
        side_spec = jax.tree_util.tree_map(
            lambda _: P(binding.dp) if binding.dp else P(), side_example
        )
        kind_specs = cache_pspecs(binding.tp)
        cache_spec = [
            jax.tree_util.tree_map(
                lambda sd, ks: P(binding.pipe, None, *ks),
                c,
                kind_specs,
                is_leaf=lambda x: isinstance(
                    x, (jax.ShapeDtypeStruct, jax.Array)
                ) or hasattr(x, "shape"),
            )
            for c in caches_sdt
        ]
        out_spec = P(binding.dp) if binding.dp else P()
        fn = shard_map(
            body,
            mesh=mesh,
            in_specs=(stacked_spec, shared_spec, side_spec, cache_spec),
            out_specs=(out_spec, cache_spec),
            check_vma=False,
        )
        return jax.jit(fn, donate_argnums=(3,) if donate else ())

    return make, program, cache_init
