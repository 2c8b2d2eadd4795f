#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from, on the chip.

  python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 12 [--control 3] [--faults 3]

One process builds the cell's compiled step once and then, for each seed,
runs the program's checked steps and the float32 reference and prints the
numbers of ``compare`` (the lower readings).  On the first ``--control``
seeds the fp8 control (the reference at e4m3 operands, put in the program's
place) is compared with the reference too (the upper readings).  On the
first ``--faults`` seeds the program is run broken underneath:

  half_batch    the feed hands the step the first half of the batch twice,
                so the mean is taken over half the rows
  no_exchange   every collective permute returns zeros (cells on more than
                one chip only; a second program is compiled for it)

A state left unchanged reads 1 on ``change_leaf`` by construction and needs
no run.  On the first ``--witness`` seeds the program is also run with its
structural ``mask`` leaves kept out of AdamW (the program freezes their
gradient but decays them), the model as the configuration states it: the
gap between its readings and the program's is the decay's share.

Each seed prints one JSON line, with each reading judged by
``compare.judge`` at the cell's committed limits (``limits/<cell>.json``).
The summary line last holds, per number, the largest program and witness
readings and the smallest control and fault readings, and per kind of run
how many seeds came out correct at those limits: all of the program's and
the witness's, none of the control's or the faults'.  The benchmark's own
runs never run this.
"""

import argparse
import json
import os
import pathlib
import sys
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class HalfBatch:
    """A feed whose second half of rows repeats its first half."""

    def __init__(self, feed):
        self.feed = feed

    def batch_at(self, step):
        b = self.feed.batch_at(step)
        half = b["tokens"].shape[0] // 2
        return {k: np.concatenate([v[:half], v[:half]]) for k, v in b.items()}




def _keep_masks(fn):
    """An AdamW step or rollback that leaves every ``mask`` leaf as it was."""
    import jax

    def is_mask(path):
        return any(getattr(k, "key", None) == "mask" for k in path)

    def wrapped(params, state, grads, cfg, scale=1.0):
        new, st = fn(params, state, grads, cfg, scale)
        keep = lambda path, old, nw: old if is_mask(path) else nw
        return jax.tree_util.tree_map_with_path(keep, params, new), st

    return wrapped


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_017)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--witness", type=int, default=3)
    args = ap.parse_args()

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(HERE))
    sys.path.insert(1, str(ROOT / "src"))
    import dataclasses

    import jax

    import cell
    import compare
    import harness
    from repro.launch.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    c = cell.load(args.workload)
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < c.chips:
        print(f"needs {c.chips} accelerator chips, found {devs}", file=sys.stderr)
        return 1

    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    bench = harness.Bench(c, devs, seeds[0])
    broken = None
    if c.chips > 1 and args.faults:
        real = jax.lax.ppermute
        jax.lax.ppermute = lambda x, *a, **k: jax.numpy.zeros_like(x)
        try:
            broken = harness.Bench(c, devs, seeds[0])
        finally:
            jax.lax.ppermute = real

    witness = None
    if args.witness:
        from repro.optim import adamw

        real = adamw.step, adamw.rollback
        adamw.step, adamw.rollback = _keep_masks(real[0]), _keep_masks(real[1])
        try:
            witness = harness.Bench(c, devs, seeds[0])
        finally:
            adamw.step, adamw.rollback = real

    kinds = ("prog", "mask_frozen", "ctrl", "half_batch", "no_exchange")
    worst = {k: {} for k in kinds}
    judged = {k: [0, 0] for k in kinds}  # [correct at the limits, runs]
    for n, seed in enumerate(seeds):
        t0 = time.perf_counter()
        bench.reseed(seed)
        state, prog = bench.check_steps()
        harness.free(state)
        ref = bench.reference()
        nums = {"prog": compare.numbers(prog, ref)}
        if n < args.witness:
            witness.reseed(seed)
            state, wit = witness.check_steps()
            harness.free(state)
            nums["mask_frozen"] = compare.numbers(wit, ref)
        if n < args.control:
            nums["ctrl"] = compare.numbers(bench.reference(quant="fp8"), ref)
        if n < args.faults:
            real_setup = bench.setup
            bench.setup = dataclasses.replace(real_setup, data=HalfBatch(bench.feed))
            state, half = bench.check_steps()
            harness.free(state)
            bench.setup = real_setup
            nums["half_batch"] = compare.numbers(half, ref)
            if broken is not None:
                broken.reseed(seed)
                state, nox = broken.check_steps()
                harness.free(state)
                nums["no_exchange"] = compare.numbers(nox, ref)
        line = {"seed": seed}
        line.update({kind: {k: v[0] for k, v in x.items()} for kind, x in nums.items()})
        line["where"] = {k: v[1] for k, v in nums["prog"].items()}
        line["correct"] = {kind: compare.judge(x, c.limits)[0] for kind, x in nums.items()}
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        for kind, x in nums.items():
            pick = max if kind in ("prog", "mask_frozen") else min
            for k, (v, _) in x.items():
                worst[kind][k] = pick(worst[kind].get(k, v), v)
            judged[kind][0] += line["correct"][kind]
            judged[kind][1] += 1
    summary = {"summary": worst, "correct_at_limits": judged, "limits": c.limits}
    summary.update(workload=args.workload, seeds=seeds)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
