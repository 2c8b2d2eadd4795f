#!/usr/bin/env python3
"""The chip benchmark's entry point: one run of one cell.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for.  Prints a progress log and, as its last lines on standard error,
each number of the check beside its limit; the last line of standard output
is the result, one JSON object.  Exits non-zero, printing no result, where
JAX finds no accelerator or fewer chips than the cell asks for, and where
the program under test (``src/repro``) is not in the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    # one compile cache, at a fixed place inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(HERE))
    sys.path.insert(1, str(ROOT / "src"))

    import cell

    c = cell.load(args.workload)
    import jax

    from repro.launch.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    devs = jax.devices()
    if devs[0].platform == "cpu":
        print("no accelerator: JAX found only CPU devices", file=sys.stderr)
        return 1
    if len(devs) < c.chips:
        print(f"the cell needs {c.chips} chips, JAX found {len(devs)}", file=sys.stderr)
        return 1

    import harness

    out = harness.run_cell(c, args.seed, args.seconds, bool(args.trace), devs, T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
