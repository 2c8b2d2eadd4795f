"""The program's own scopes and feed spans, read from a profile of its step.

The program names the parts of its step with ``jax.named_scope``, which
puts the name into each device op's HLO ``op_name`` and costs the device
nothing: ``F``, ``B`` and ``W`` (the executor's passes), ``head_ce`` (the
LM head and its loss, inside ``F``, ``B`` and ``W``), ``attn`` (q, k, v to
the attention output), ``send`` (the pipe's collective permutes in the
tick), ``opt`` (the optimizer tail) and ``sync`` (the reductions over the
pipe after the last tick and in post-validation, inside ``opt``): these
are ``PROGRAM_SCOPES``.  A reader module ``metrics/<metric>.py`` may state
further scopes that it reads, ``SCOPES = ("flash",)``: ``known_scopes``
adds each, once per process, so a scope the program gains is read by a new
metric file alone.  A scope path keeps the known scopes among an op's
``op_name`` components, outermost first (``B/attn/flash``); any other
component (``jit(body)``, ``shard_map``, ``while``) is dropped.  Its
feed writes host spans on the device ops' clock
(``jax.profiler.TraceAnnotation``): ``repro.feed`` around
``repro.feed.batch``, ``repro.feed.build`` and ``repro.feed.put``.

``trace_reduce.Trace``, which the harness hands the readers, keeps neither,
and the harness deletes its profile before the readers run.  So
``profile(run)`` takes one of its own, once per traced run, after the
check: the cell's step built again through ``harness.Bench`` (a hit in the
compile cache) and ``STEPS`` steps of it under the profiler, with the
harness's own ``bench.*`` spans.  ``extract`` keeps each device op with
its HLO module and each ``bench.*`` and ``repro.*`` span; the reductions
below read only that, so a hand-made or recorded ``Scoped`` checks them
without a chip.  Where the step carries no scope (a program from before
them) nothing is profiled and every reader reports nothing; so it is on a
CPU, which has no device planes.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import pathlib
import re
import shutil
import sys
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

import cell
import trace_reduce
from trace_reduce import DEVICE_PLANE, OPS_LINE, Event, busy_intervals, self_times

PROGRAM_SCOPES = ("F", "B", "W", "opt", "head_ce", "attn", "send", "sync")
WAITS = ("send", "sync")  # scopes of the collectives a stage waits in
SPAN_PREFIXES = ("bench.", "repro.")
MODULES_LINE = "XLA Modules"
STEPS = 4  # profiled steps, after one unprofiled step of the same executable
SEED = 0  # the profile's weights and tokens; a step's time does not read them

Op = Tuple[str, str, float, float]  # (instruction, HLO module, start ns, duration ns)
Interval = Tuple[float, float]


@dataclasses.dataclass
class Scoped:
    ops: Dict[str, List[Op]]  # device plane -> every op it ran, of any module
    spans: List[Event]  # bench.* and repro.* host spans
    step_module: str  # the HLO module of the train step
    scopes: Dict[str, str]  # instruction of the step -> its scope path, "B/attn"
    steps: int

    @classmethod
    def from_json(cls, d: dict) -> "Scoped":
        ops = {k: [(str(n), str(m), float(s), float(t)) for n, m, s, t in v] for k, v in d["ops"].items()}
        spans = [(str(n), float(s), float(t)) for n, s, t in d["spans"]]
        return cls(ops, spans, d["step_module"], dict(d["scopes"]), int(d["steps"]))


# --------------------------------------------------------------------- #
# scopes from the HLO text
# --------------------------------------------------------------------- #
_WRAPPED = re.compile(r"\w+\((.*)\)")  # jvp(attn), transpose(jvp(attn))
# an instruction starts a line; its attributes may run over several lines, as
# a Pallas call's kernel_metadata does, up to the next instruction
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", re.M)
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)


@functools.lru_cache(maxsize=None)
def known_scopes(root: pathlib.Path = cell.ROOT) -> Tuple[str, ...]:
    """``PROGRAM_SCOPES``, then each scope that a reader in ``metrics/``
    under the checkout ``root`` states as its ``SCOPES``, by file name."""
    out = list(PROGRAM_SCOPES)
    for path in sorted(cell.metrics_dir(root).glob("*.py")):
        stated = getattr(cell.load_module(path, f"bench_metric_{path.stem}"), "SCOPES", ())
        names = not isinstance(stated, str) and all(isinstance(sc, str) and sc and "/" not in sc for sc in stated)
        if not names:
            raise ValueError(f"{path}: SCOPES is a tuple of scope names, not {stated!r}")
        out += [sc for sc in stated if sc not in out]
    return tuple(out)


def scope_path(op_name: str, root: pathlib.Path = cell.ROOT) -> str:
    """The known scopes (of the checkout ``root``) among the components of
    an ``op_name``, outermost first, each taken out of the ``jvp(...)`` /
    ``transpose(...)`` that differentiation wraps it in:
    ``jit(body)/.../B/transpose(jvp(attn))/dot`` -> ``B/attn``."""
    scopes = known_scopes(root)
    out = []
    for part in op_name.split("/"):
        m = _WRAPPED.fullmatch(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.fullmatch(part)
        if part in scopes:
            out.append(part)
    return "/".join(out)


def hlo_scopes(hlo_text: str, root: pathlib.Path = cell.ROOT) -> Tuple[str, Dict[str, str]]:
    """(module name, instruction -> scope path) of a compiled HLO module,
    for each instruction with an ``op_name``."""
    module = _MODULE.search(hlo_text)
    starts = list(_INSTR.finditer(hlo_text))
    ends = [m.start() for m in starts[1:]] + [len(hlo_text)]
    paths = {}
    for m, end in zip(starts, ends):
        op = _OP_NAME.search(hlo_text, m.end(), end)
        if op:
            paths[m.group(1)] = scope_path(op.group(1), root)
    return (module.group(1) if module else "", paths)


def in_scope(path: str, scope: str) -> bool:
    return scope in path.split("/")


# --------------------------------------------------------------------- #
# the profile
# --------------------------------------------------------------------- #
def _module_name(event_name: str) -> str:
    """``jit_body(12)`` -> ``jit_body``: the XLA Modules line names a run of
    a module with its program id."""
    return re.sub(r"\(\d+\)$", "", event_name)


def extract(xplane_path: str, hlo_text: str, steps: int) -> Scoped:
    """Every device op with its HLO module, and the bench.* and repro.*
    spans, of the profile at ``xplane_path`` of ``steps`` steps of the
    module printed in ``hlo_text``."""
    import jax

    step_module, scopes = hlo_scopes(hlo_text)
    data = jax.profiler.ProfileData.from_file(xplane_path)
    ops: Dict[str, List[Op]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name) and "CPU" not in plane.name:
            lines = {line.name: list(line.events) for line in plane.lines}
            runs = sorted(
                (float(e.start_ns), float(e.start_ns + e.duration_ns), _module_name(e.name))
                for e in lines.get(MODULES_LINE, [])
            )
            evs = []
            for e in lines.get(OPS_LINE, []):
                s = float(e.start_ns)
                evs.append((trace_reduce.op_name(e.name).lstrip("%"), _module_at(runs, s), s, float(e.duration_ns)))
            ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIXES)
                )
    spans.sort(key=lambda e: (e[1], -e[2]))
    used = {n for evs in ops.values() for n, m, _, _ in evs if m == step_module}
    return Scoped(ops, spans, step_module, {n: p for n, p in scopes.items() if n in used}, steps)


def _module_at(runs: List[Tuple[float, float, str]], t: float) -> str:
    """The module whose run (sorted, disjoint) holds time ``t``; "" if none."""
    i = bisect.bisect_right(runs, (t, float("inf"), "")) - 1
    return runs[i][2] if i >= 0 and t < runs[i][1] else ""


# [run, Scoped or None]: every reader of a run gets only the run, and the
# run's profile is taken once, by the first of them
_last: List = []


def profile(run, log=lambda s: print(s, file=sys.stderr, flush=True)) -> Optional[Scoped]:
    """The profile of the cell of ``run`` (a ``harness.RunInfo``), taken on
    the first call for that run; None where nothing can be read."""
    if run.trace is None:
        return None
    if not _last or _last[0] is not run:
        _last[:] = [run, _take(run, log)]
    return _last[1]


def _take(run, log) -> Optional[Scoped]:
    import jax

    import harness

    c = cell.Cell("scoped", run.chips, run.config, run.arch, run.traffic, {}, [], [])
    bench = harness.Bench(c, jax.devices(), SEED)
    text = bench.step.as_text()
    if not any(hlo_scopes(text)[1].values()):
        log("scopes: the step carries none; no scoped metric is read")
        return None
    state, _ = bench.run_step(bench.init_state(), 0)
    tmp = tempfile.mkdtemp(prefix="bench_scoped_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            for i in range(1, STEPS + 1):
                state, _ = bench.run_step(state, i, jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        harness.free(state)
        s = extract(trace_reduce.find_xplane(tmp), text, STEPS)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if not s.ops:
        log("scopes: no device planes in the profile; no scoped metric is read")
        return None
    for line in report(s):
        log(line)
    return s


def reading(run, fn):
    """``fn`` of the profile of ``run``; None where there is none."""
    s = profile(run)
    return None if s is None else fn(s)


# --------------------------------------------------------------------- #
# reductions
# --------------------------------------------------------------------- #
def window(s: Scoped) -> Interval:
    """First bench.* span's start to the last one's end, as the harness's."""
    bench = [e for e in s.spans if e[0].startswith("bench.")]
    if not bench:
        raise ValueError("no bench.* spans in the profile")
    return bench[0][1], max(t + d for _, t, d in bench)


def _measure(ivs: Sequence[Interval]) -> float:
    return sum(e - s for s, e in ivs)


def _step_ops(s: Scoped, plane: str, scope: Optional[str] = None) -> List[Event]:
    """The step's ops on one chip, as (name, start, duration); those under
    ``scope`` only where it is given."""
    return [
        (n, t, d)
        for n, m, t, d in s.ops[plane]
        if m == s.step_module and (scope is None or in_scope(s.scopes.get(n, ""), scope))
    ]


def scope_seconds(s: Scoped, scope: str) -> Optional[Dict[str, float]]:
    """Per chip, the seconds in the window in which an op under ``scope``
    runs (union of their intervals); None where no chip ran one."""
    t0, t1 = window(s)
    out, found = {}, False
    for plane in s.ops:
        sel = _step_ops(s, plane, scope)
        found = found or bool(sel)
        out[plane] = _measure(busy_intervals(sel, t0, t1)) / 1e9
    return out if found else None


def scope_ms(s: Scoped, scope: str) -> Optional[float]:
    """ms per step under ``scope``, mean over chips."""
    per = scope_seconds(s, scope)
    return None if per is None else 1e3 * sum(per.values()) / len(per) / s.steps


def stage_idle(s: Scoped) -> Dict[str, float]:
    """Per chip, the share of the window in which no op but a collective of
    the pipe runs: 1 - |union of every op's interval less that of the ops
    under ``send`` or ``sync``| / window.  A stage that waits for its
    neighbour waits inside a send, and one that is done before the others
    waits inside the reductions after the last tick, so this is the
    pipeline's bubble as the chip shows it, with the transfers."""
    t0, t1 = window(s)
    out = {}
    for plane, evs in s.ops.items():
        busy = _measure(busy_intervals([(n, t, d) for n, _, t, d in evs], t0, t1))
        waits = [op for sc in WAITS for op in _step_ops(s, plane, sc)]
        out[plane] = 1.0 - (busy - _measure(busy_intervals(waits, t0, t1))) / (t1 - t0)
    return out


def stage_idle_share(s: Scoped) -> Optional[float]:
    """``stage_idle`` in %, mean over chips; None where nothing is sent."""
    if scope_seconds(s, "send") is None:
        return None
    per = stage_idle(s)
    return 100.0 * sum(per.values()) / len(per)


def feed_wait(s: Scoped) -> Optional[Dict[str, float]]:
    """Per chip, the seconds inside ``repro.feed`` spans in which no op of
    any module runs on it; None where the feed wrote no span."""
    feed = [(t, t + d) for n, t, d in s.spans if n == "repro.feed"]
    if not feed:
        return None
    out = {}
    for plane, evs in s.ops.items():
        ivs = [(n, t, d) for n, _, t, d in evs]
        out[plane] = sum((e - b) - _measure(busy_intervals(ivs, b, e)) for b, e in feed) / 1e9
    return out


def feed_wait_ms(s: Scoped) -> Optional[float]:
    per = feed_wait(s)
    if not per:
        return None
    return 1e3 * sum(per.values()) / len(per) / s.steps


def _bare(s: Scoped, plane: str) -> Tuple[Dict[str, float], float]:
    """(self ns in the window of each step op under no known scope, self ns
    of every op) on one chip."""
    t0, t1 = window(s)
    own = self_times([(name, t, d) for name, _, t, d in s.ops[plane]], t0, t1)
    step = {name for name, m, _, _ in s.ops[plane] if m == s.step_module}
    return {k: v for k, v in own.items() if k in step and not s.scopes.get(k)}, sum(own.values())


def unscoped(s: Scoped, plane: str, n: int = 3) -> Tuple[float, List[List]]:
    """(share of the chip's busy time in step ops under no scope, the ``n``
    of them with the most self time in ms per step)."""
    bare, total = _bare(s, plane)
    top = sorted(bare.items(), key=lambda kv: -kv[1])[:n]
    share = sum(bare.values()) / total if total else 0.0
    return share, [[k, v / 1e6 / s.steps] for k, v in top]


def unscoped_ms(s: Scoped) -> float:
    """Self time of the step's ops under no known scope, ms per step, mean
    over chips: the time ``unscoped`` gives as a share."""
    return sum(sum(_bare(s, plane)[0].values()) for plane in s.ops) / 1e6 / len(s.ops) / s.steps


def named_gaps(s: Scoped, n: int = 5) -> List[List]:
    """The ``n`` longest idle gaps of any chip, each named by the chain of
    spans around its midpoint, outermost first: ``bench.side_at>repro.feed.put``."""
    t0, t1 = window(s)
    gaps = []
    for evs in s.ops.values():
        edge = t0
        for b, e in busy_intervals([(n_, t, d) for n_, _, t, d in evs], t0, t1) + [(t1, t1)]:
            if b > edge:
                gaps.append((edge, b))
            edge = max(edge, e)
    out = []
    for b, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (b + e) / 2
        chain = [name for name, t, d in s.spans if t <= mid < t + d]
        out.append([">".join(chain) or "host.other", (e - b) / 1e9])
    return out


def report(s: Scoped) -> List[str]:
    """The log lines of a profile: per chip, ms per step of each scope, the
    stage's idle share, the share of busy time under no scope and its three
    largest ops; then the longest idle gaps by span."""
    per = {sc: scope_seconds(s, sc) or {} for sc in known_scopes()}
    idle = stage_idle(s)
    lines = [f"scopes: {s.steps} profiled steps, ms per step by chip"]
    for plane in s.ops:
        cells = " ".join(f"{sc} {1e3 * ms.get(plane, 0.0) / s.steps:.3f}" for sc, ms in per.items())
        share, top = unscoped(s, plane)
        tops = ", ".join(f"{k} {v:.3f}" for k, v in top)
        lines.append(
            f"scopes {plane}: {cells} | stage idle {100 * idle[plane]:.3f}% | "
            f"unscoped {100 * share:.3f}% of busy, largest: {tops}"
        )
    gaps = ", ".join(f"{name} {1e3 * sec:.3f} ms" for name, sec in named_gaps(s))
    lines.append(f"scopes: longest idle gaps: {gaps}")
    return lines
