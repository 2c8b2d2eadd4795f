"""CPU tests of the benchmark: ``python -m pytest benchmarks/chip``.

Four virtual CPU devices, for the four-chip cell's pipeline.
"""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = f"{_flags} --xla_force_host_platform_device_count=4".strip()

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))
