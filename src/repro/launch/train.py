"""End-to-end training launcher.

Builds (config, schedule, mesh) -> ZB train step compiled ahead of the loop
-> fault-tolerant driver loop (checkpoints with ``--ckpt-dir``).  ``train()``
is the one entry point: the CLI below and ``chip_smoke.py`` both call it.
Works on any mesh whose axis names match the binding -- CPU test meshes
(fake devices), one TPU chip or a four-chip host, and the production
(16,16) / (2,16,16) meshes alike.

Example (small CPU run, 4 fake devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  python -m repro.launch.train --arch internlm2_1_8b --reduced \
      --pipe-size 4 --steps 30 --schedule zb-h2
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import get_config, get_reduced
from ..core.schedules import (
    compile_plan,
    one_f_one_b,
    v_half,
    v_min,
    zb_1p,
    zb_2p,
    zb_h1,
    zb_h2,
    zb_v,
)
from ..data import DataConfig, SyntheticLM
from ..models.lm import ArchConfig, RunSpec, init_params
from ..optim import adamw
from ..runtime import DriverConfig, TrainDriver
from .compile_cache import enable_persistent_cache
from .mesh import AxisBinding, make_train_mesh
from .steps import TrainStepConfig, build_train_step

SCHEDULES = {
    "1f1b": one_f_one_b,
    "zb-h1": zb_h1,
    "zb-h2": zb_h2,
    "zb-v": zb_v,
    "v-min": v_min,
    "v-half": v_half,
    "zb-1p": zb_1p,
    "zb-2p": zb_2p,
}


def build_everything(
    cfg: ArchConfig,
    schedule: str,
    mesh,
    binding: AxisBinding,
    microbatch: int,
    seq_len: int,
    m: int,
    tcfg: TrainStepConfig,
    memory_budget_bytes=None,
):
    """(spec, sched, make, in_specs) of the train step on ``mesh``.

    ``make(side_example)`` returns the jitted step; ``in_specs`` are its
    PartitionSpecs (params, shared, opt, shared_opt, side).
    """
    pipe_size, tp_size, dp_size = binding.sizes(mesh)
    if memory_budget_bytes is not None:
        from ..runtime.driver import replan_under_budget

        sched, report = replan_under_budget(
            cfg, pipe_size, m, microbatch, seq_len, memory_budget_bytes,
            tp_size=tp_size, dp_size=dp_size,
        )
        print(f"HBM planner: {report.summary()}")
        if report.chosen is not None and report.chosen.breakdown is not None:
            print("per-device HBM breakdown:")
            print(report.chosen.breakdown.report())
    else:
        sched = SCHEDULES[schedule](pipe_size, m)
    plan = compile_plan(sched)
    spec = RunSpec(
        p=pipe_size,
        n_chunks=sched.n_chunks,
        microbatch=microbatch,
        seq_len=seq_len,
        m=m,
        tp_axis=binding.tp,
        tp_size=tp_size,
    )
    make, (in_specs, _) = build_train_step(
        cfg, spec, plan, sched.placement, mesh, binding, tcfg
    )
    return spec, sched, make, in_specs


def _state_tree(stacked, shared, opt, shared_opt):
    return dict(params=stacked, shared=shared, opt=opt, shared_opt=shared_opt)


def _init_state(cfg, spec, placement, seed):
    stacked, shared = init_params(cfg, spec, placement, jax.random.PRNGKey(seed))
    return _state_tree(stacked, shared, adamw.init(stacked), adamw.init(shared))


def _abstract(tree, shardings):
    return jax.tree_util.tree_map(
        lambda sd, sh: jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=sh),
        tree,
        shardings,
    )


@dataclasses.dataclass
class StepSetup:
    """The train step on one mesh and what feeds it."""

    cfg: ArchConfig
    spec: RunSpec
    sched: Any
    make: Any
    state_shardings: Dict[str, Any]  # the step's own input layout
    side_sharding: NamedSharding
    data: SyntheticLM

    def side_at(self, step_idx: int):
        """The step's side inputs on the mesh.  Its spans (``repro.feed.*``)
        show in a profiler trace, on the clock of the device ops."""
        with TraceAnnotation("repro.feed"):
            with TraceAnnotation("repro.feed.batch"):
                batch = self.data.batch_at(step_idx)
            with TraceAnnotation("repro.feed.build"):
                side = side_from_batch(batch, self.spec, cfg=self.cfg)
            with TraceAnnotation("repro.feed.put"):
                return jax.device_put(side, self.side_sharding)

    def init_state(self, seed: int = 0):
        """Params + AdamW moments, created on the mesh already laid out as
        the step takes them: nothing passes through one default device, and
        the step's donation aliases every leaf.  Fresh arrays on every call."""
        cfg, spec, placement = self.cfg, self.spec, self.sched.placement
        return jax.jit(
            lambda: _init_state(cfg, spec, placement, seed),
            out_shardings=self.state_shardings,
        )()

    def lower(self):
        """The step lowered from abstract state and side: no array is made,
        so the mesh may hold the devices of a described topology."""
        cfg, spec, placement = self.cfg, self.spec, self.sched.placement
        state = _abstract(
            jax.eval_shape(lambda: _init_state(cfg, spec, placement, 0)),
            self.state_shardings,
        )
        side = jax.eval_shape(
            lambda: side_from_batch(self.data.batch_at(0), spec, cfg=cfg)
        )
        side = _abstract(
            side, jax.tree_util.tree_map(lambda _: self.side_sharding, side)
        )
        return self.make(side).lower(
            state["params"], state["shared"], state["opt"], state["shared_opt"], side
        )


def setup_train_step(
    cfg: ArchConfig,
    schedule: str,
    mesh,
    *,
    binding: AxisBinding,
    microbatch: int = 2,
    seq_len: int = 64,
    m: int = 8,
    tcfg: Optional[TrainStepConfig] = None,
    seed: int = 0,
    memory_budget_bytes=None,
) -> StepSetup:
    """The train step of ``schedule`` on ``mesh``; ``train()`` and the
    compile rehearsal of ``chip_smoke.py`` both lower it from here."""
    spec, sched, make, in_specs = build_everything(
        cfg, schedule, mesh, binding, microbatch, seq_len, m,
        tcfg or TrainStepConfig(), memory_budget_bytes=memory_budget_bytes,
    )
    state_shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        _state_tree(*in_specs[:4]),
        is_leaf=lambda x: isinstance(x, P),
    )
    data = SyntheticLM(
        DataConfig(
            global_batch=spec.m * spec.microbatch,
            seq_len=spec.seq_len,
            vocab=cfg.vocab,
            seed=seed,
        )
    )
    return StepSetup(
        cfg, spec, sched, make, state_shardings, NamedSharding(mesh, in_specs[4]), data
    )


def side_from_batch(batch, spec, s_total_extra=None, cfg=None):
    m, b, s = spec.m, spec.microbatch, spec.seq_len
    tokens = jnp.asarray(batch["tokens"]).reshape(m, b, s)
    labels = jnp.asarray(batch["labels"]).reshape(m, b, s)
    side = {"tokens": tokens, "labels": labels}
    s_total = s
    if cfg is not None and cfg.family == "encdec":
        ex = cfg.extras_dict()
        side["frames"] = jnp.zeros(
            (m, b, ex["s_enc"], ex.get("frontend_dim", cfg.d_model)), cfg.jdtype()
        )
        s_total += ex["s_enc"]
    if cfg is not None and cfg.family == "vlm":
        ex = cfg.extras_dict()
        side["patches"] = jnp.zeros(
            (m, b, ex["n_patches"], ex.get("frontend_dim", cfg.d_model)), cfg.jdtype()
        )
        s_total += ex["n_patches"]
    side["positions"] = jnp.broadcast_to(jnp.arange(s_total), (m, s_total))
    return side


@dataclasses.dataclass
class TrainRun:
    losses: List[float]
    grad_norms: List[float]  # global norm of each step's gradient, pre-clip
    step_seconds: List[float]  # each ended by block_until_ready
    compile_seconds: float
    schedule: str
    state: Dict[str, Any]


def train(
    cfg: ArchConfig,
    schedule: str,
    mesh,
    steps: int,
    *,
    binding: AxisBinding,
    microbatch: int = 2,
    seq_len: int = 64,
    m: int = 8,
    tcfg: Optional[TrainStepConfig] = None,
    seed: int = 0,
    ckpt_dir: Optional[str] = None,
    max_retries: int = 3,
    memory_budget_bytes=None,
) -> TrainRun:
    """Train ``cfg`` for ``steps`` steps of ``schedule`` on ``mesh``.

    The step is compiled ahead of the loop (``compile_seconds``), so every
    entry of ``step_seconds`` is a run of the compiled program.
    """
    setup = setup_train_step(
        cfg, schedule, mesh, binding=binding, microbatch=microbatch,
        seq_len=seq_len, m=m, tcfg=tcfg, seed=seed,
        memory_budget_bytes=memory_budget_bytes,
    )
    t0 = time.perf_counter()
    step = setup.lower().compile()
    compile_s = time.perf_counter() - t0

    def step_fn(state, side):
        p_, sh, o, so, metrics = step(
            state["params"], state["shared"], state["opt"], state["shared_opt"],
            side,
        )
        return _state_tree(p_, sh, o, so), metrics

    driver = TrainDriver(
        DriverConfig(
            ckpt_dir=ckpt_dir,
            ckpt_every=max(steps // 2, 10),
            max_retries=max_retries,
        ),
        step_fn,
        lambda: setup.init_state(seed),
        setup.side_at,
    )
    state, metrics = driver.run(steps)
    return TrainRun(
        losses=[float(mt["loss"]) for _, mt in metrics],
        grad_norms=[float(mt["grad_norm"]) for _, mt in metrics],
        step_seconds=list(driver.step_times),
        compile_seconds=compile_s,
        schedule=setup.sched.name,
        state=state,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt3_1_5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--pipe-size", type=int, default=4)
    ap.add_argument("--tp-size", type=int, default=1)
    ap.add_argument("--schedule", default="zb-h2", choices=sorted(SCHEDULES))
    ap.add_argument("--microbatch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--m", type=int, default=8)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument(
        "--ckpt-dir", default=None, help="checkpoint directory (default: none)"
    )
    ap.add_argument("--postval", default="within_step", choices=["within_step", "sync"])
    ap.add_argument(
        "--executor",
        default="specialized",
        choices=["scan", "unroll", "specialized"],
        help="executor compilation mode (DESIGN.md Sec. 8): 'specialized' "
        "unrolls the tick stream against the static plan (fastest steps, "
        "slowest first compile -- amortized by the persistent cache); "
        "'scan' is the generic one-tick-body baseline",
    )
    ap.add_argument(
        "--no-donate",
        action="store_true",
        help="keep params/opt-state buffers undonated (doubles their peak)",
    )
    ap.add_argument(
        "--memory-budget-mb",
        type=float,
        default=None,
        help="per-device HBM budget: params + zero1 optimizer state + "
        "channel/inbox/sink buffers + schedule memory; picks the fastest "
        "schedule across all families that fits (overrides --schedule); "
        "plans are reused across processes via $REPRO_PLAN_CACHE_DIR",
    )
    args = ap.parse_args()

    # repeated runs skip recompiles
    enable_persistent_cache()
    tcfg = TrainStepConfig(
        adamw=adamw.AdamWConfig(lr=args.lr),
        postval_mode=args.postval,
        executor_mode=args.executor,
        donate=not args.no_donate,
    )
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    mesh, binding = make_train_mesh(args.pipe_size, args.tp_size)
    t0 = time.time()
    run = train(
        cfg,
        args.schedule,
        mesh,
        args.steps,
        binding=binding,
        microbatch=args.microbatch,
        seq_len=args.seq_len,
        m=args.m,
        tcfg=tcfg,
        ckpt_dir=args.ckpt_dir,
        memory_budget_bytes=(
            args.memory_budget_mb * 2**20
            if args.memory_budget_mb is not None
            else None
        ),
    )
    dt = time.time() - t0
    losses = run.losses
    steady = run.step_seconds[1:]
    tput_s = f" steps/s={len(steady) / sum(steady):.3f}" if steady else ""
    print(f"steps={len(losses)} wall={dt:.1f}s compile={run.compile_seconds:.1f}s"
          f"{tput_s} loss[0]={losses[0]:.4f} loss[-1]={losses[-1]:.4f} "
          f"schedule={run.schedule} executor={args.executor}")
    assert losses[-1] < losses[0], "loss must decrease on the synthetic stream"


if __name__ == "__main__":
    main()
