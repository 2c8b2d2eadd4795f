"""Every collective of a four-stage train step sits under the executor's
``send`` scope (the tick's permutes) or under ``opt/sync`` (the reductions
over the pipe after the last tick and post-validation's prefix statistics);
subprocess, fake devices.

  python send_scopes.py <schedule> <executor mode>

Compiles the step on four virtual CPU devices and prints OK with the count
of the permutes under ``send`` and of the collectives under ``sync``.
"""

import os
import re
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["REPRO_PLAN_CACHE_DIR"] = "off"

from repro.configs import get_reduced  # noqa: E402
from repro.launch.mesh import make_train_mesh  # noqa: E402
from repro.launch.steps import TrainStepConfig  # noqa: E402
from repro.launch.train import setup_train_step  # noqa: E402

PERMUTE = re.compile(r"= .* collective-permute(?:-start)?\(.*op_name=\"([^\"]*)\"")
REDUCE = re.compile(r"= .* all-reduce(?:-start)?\(.*op_name=\"([^\"]*)\"")


def main():
    schedule, mode = sys.argv[1], sys.argv[2]
    mesh, binding = make_train_mesh(4)
    setup = setup_train_step(
        get_reduced("internlm2_1_8b"), schedule, mesh, binding=binding,
        microbatch=1, seq_len=16, m=8, tcfg=TrainStepConfig(executor_mode=mode),
    )
    text = setup.lower().compile().as_text()
    names = PERMUTE.findall(text)
    assert names, "no collective permute in the step"
    scoped = lambda name, scope: scope in name.split("/")
    sends = [n for n in names if scoped(n, "send")]
    assert sends, "no collective permute under send"
    rest = [n for n in names if not scoped(n, "send")] + REDUCE.findall(text)
    syncs = [n for n in rest if scoped(n, "opt") and scoped(n, "sync")]
    assert syncs, "no collective under opt/sync"
    bare = [n for n in rest if n not in syncs]
    assert not bare, f"collectives outside send and opt/sync: {bare[:5]}"
    print(f"OK {len(sends)} collective permutes under send, {len(syncs)} collectives under sync")


if __name__ == "__main__":
    main()
