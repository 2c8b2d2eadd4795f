"""One run of a cell: set-up, the measured window, the check.

Set-up builds one object, the compiled train step of the program
(``repro.launch.train.setup_train_step(...).lower().compile()``) with its
state made from the seed on the mesh, and drives it through its first
``check_steps`` steps through the window's own call and feed, reading the
first gradient and the change of the weights from its state.  The window
then runs that same step on that same state, closed loop: data, step,
``block_until_ready``, until ``seconds`` have passed.  After the window the
state is freed and the float32 reference follows the first steps from the
same seed; ``compare`` decides ``correct``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

import compare
import peaks
import trace_reduce
from cell import ROOT, Cell, load_module, metrics_dir
from reference import Reference
from traffic import TokenBatches, seed_words
from weights import ProgramLayout


@dataclasses.dataclass
class RunInfo:
    """What a per-layer metric reader may read."""

    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    steps: int
    window_s: float
    tokens_per_step: int
    peak: Optional[dict]
    trace: Optional[trace_reduce.Trace]
    arch: Any = None  # the configuration's archs/<architecture> module


class CompileCounter:
    """Counts traces and compiles (JAX's monitoring events) while on."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration", "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on and name in self.EVENTS:
            self.count += 1


class Bench:
    """The cell's compiled train step and what feeds and reads it."""

    def __init__(self, cell: Cell, devices, seed: int):
        from repro.launch.mesh import make_train_mesh
        from repro.launch.steps import TrainStepConfig
        from repro.launch.train import setup_train_step
        from repro.optim.adamw import AdamWConfig

        self.cell = cell
        t = cell.traffic
        self.devices = list(devices[: cell.chips])
        mesh, binding = make_train_mesh(t["pipe"], devices=self.devices)
        setup = setup_train_step(
            cell.arch.arch_config(cell.config),
            t["schedule"],
            mesh,
            binding=binding,
            microbatch=t["microbatch_size"],
            seq_len=t["seq_len"],
            m=t["microbatches"],
            tcfg=TrainStepConfig(
                adamw=AdamWConfig(**t["optimizer"]), executor_mode=t["executor"]
            ),
        )
        self.setup = setup
        self.reseed(seed)
        self.step = self.setup.lower().compile()
        self.layout = ProgramLayout(cell.arch, cell.config, self.setup)
        self._init = self.layout.init_fn()
        self._moments = self.layout.moment_norms_fn()
        self._change = self.layout.change_norms_fn()

    def reseed(self, seed: int) -> None:
        """Weights and batches from ``seed``; the compiled step stays."""
        self.feed = TokenBatches(self.cell.traffic, self.cell.config["vocab_size"], seed)
        self.setup = dataclasses.replace(self.setup, data=self.feed)
        self.key_words = seed_words(seed)

    def hbm_bytes(self) -> int:
        """The compiler's peak of the step on the fullest chip."""
        return int(self.step.memory_analysis().peak_memory_in_bytes)

    def init_state(self):
        return self._init(self.key_words)

    def run_step(self, state, i: int, span=contextlib.nullcontext):
        with span("bench.side_at"):
            side = self.setup.side_at(i)
        with span("bench.step"):
            p, sh, o, so, metrics = self.step(
                state["params"], state["shared"], state["opt"], state["shared_opt"], side
            )
        with span("bench.block"):
            jax.block_until_ready((p, sh, o, so, metrics))
        return dict(params=p, shared=sh, opt=o, shared_opt=so), metrics

    def check_steps(self):
        """The first steps from the seed, read for the check: the state after
        them and the program's readings (``Reference.run``'s format)."""
        k = self.cell.traffic["check_steps"]
        b1 = self.cell.traffic["optimizer"]["b1"]
        state = self.init_state()
        losses, gnorms, g1 = [], [], None
        for i in range(k):
            state, metrics = self.run_step(state, i)
            losses.append(float(metrics["loss"]))
            gnorms.append(float(metrics["grad_norm"]))
            if i == 0:
                m = self._moments(state["opt"].m, state["shared_opt"].m)
                g1 = {n: np.asarray(v) / (1.0 - b1) for n, v in m.items()}
        change = self._change(state["params"], state["shared"], self.key_words)
        readings = {
            "losses": losses,
            "grad_norms": gnorms,
            "grad_leaf": g1,
            "change_leaf": {n: np.asarray(v) for n, v in change.items()},
        }
        return state, readings

    def reference_batches(self):
        t = self.cell.traffic
        shape = (t["microbatches"], t["microbatch_size"], t["seq_len"])
        return [
            {k: v.reshape(shape) for k, v in self.feed.batch_at(i).items()}
            for i in range(t["check_steps"])
        ]

    def reference(self, quant=None) -> Dict[str, Any]:
        ref = Reference(self.cell.arch, self.cell.config, self.cell.traffic["optimizer"], quant=quant)
        return ref.run(self.key_words, self.reference_batches(), self.devices)


def free(tree) -> None:
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            leaf.delete()


def load_reader(name: str, root: pathlib.Path = ROOT):
    """``read`` of the per-layer metric ``name``, from ``metrics/`` under the
    checkout ``root``."""
    return load_module(metrics_dir(root) / f"{name}.py", f"bench_metric_{name}").read


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    devices,
    t_start: float,
    log=lambda s: print(s, file=sys.stderr, flush=True),
) -> Dict[str, Any]:
    """One run; returns the result line's object."""
    counter = CompileCounter()
    bench = Bench(cell, devices, seed)
    hbm = bench.hbm_bytes()
    state, readings = bench.check_steps()
    log(f"check steps: losses {readings['losses']} grad norms {readings['grad_norms']}")

    # ---- the measured window ------------------------------------------ #
    tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    span = jax.profiler.TraceAnnotation if trace else contextlib.nullcontext
    if trace:
        jax.profiler.start_trace(tmp)
    counter.on = True
    losses: List[Any] = []
    i = cell.traffic["check_steps"]
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    while True:
        state, metrics = bench.run_step(state, i, span)
        losses.append(metrics["loss"])
        i += 1
        window_s = time.perf_counter() - t0
        if window_s >= seconds:
            break
    counter.on = False
    steps = len(losses)
    failed = sum(not np.isfinite(float(x)) for x in losses)
    tr = None
    if trace:
        jax.profiler.stop_trace()
        tr = trace_reduce.extract(trace_reduce.find_xplane(tmp))
        shutil.rmtree(tmp, ignore_errors=True)
    log(f"window: {steps} steps in {window_s!r} s, {counter.count} compiles")

    stats = [d.memory_stats() or {} for d in bench.devices]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    free(state)
    del state, metrics, losses

    # ---- the check ---------------------------------------------------- #
    t_ref = time.perf_counter()
    ref = bench.reference()
    log(f"reference: {time.perf_counter() - t_ref:.1f} s, losses {ref['losses']} "
        f"grad norms {ref['grad_norms']}")
    correct, checks = compare.judge(compare.numbers(readings, ref), cell.limits)

    dev = bench.devices[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": mem_peak,
    }
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": steps, "failed": failed}
    if not trace:
        values = {
            "tokens_per_s": steps * cell.tokens_per_step / window_s / cell.chips,
            "step_hbm_gb": hbm / 1e9,
            "setup_s": setup_s,
        }
        out["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in cell.end_to_end
        }
    else:
        info = RunInfo(
            config=cell.config,
            traffic=cell.traffic,
            chips=cell.chips,
            steps=steps,
            window_s=window_s,
            tokens_per_step=cell.tokens_per_step,
            peak=peaks.peak(dev.device_kind),
            trace=tr,
            arch=cell.arch,
        )
        out["metrics"] = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(info)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        busy = trace_reduce.busy_seconds(tr)
        t_lo, t_hi = trace_reduce.window(tr)
        device["busy_s"] = busy if busy is not None else 0.0
        device["window_s"] = (t_hi - t_lo) / 1e9
        out["breakdown"] = {
            "device_ops": trace_reduce.top_ops(tr),
            "idle_gaps": trace_reduce.idle_gaps(tr),
        }
    out["device"] = device
    out["window_compiles"] = counter.count
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r} ({c['at']})")
    return out
