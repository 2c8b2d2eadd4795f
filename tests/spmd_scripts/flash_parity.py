"""The train step with causal attention on the blocked kernel computes the
step of the jnp attention path, on a multi-device mesh; subprocess, fake
devices.

  python flash_parity.py <schedule> <executor mode> <pipe> <tp> <kv heads>

Trains one step twice on four virtual CPU devices: once on the jnp path
(the CPU's own), once with the kernel taken as on the TPU and run by the
Pallas interpreter.  Prints OK with both losses and gradient norms when
they agree and the kernel's ops (``attn/flash``) are in the second step
only.  4 q heads of 128: with tp 2 each device holds 2 q heads over 1 kv
head (kv sharded with q, grouped in place); with tp 4 and 2 kv heads the
kv heads are replicated and each device selects its q head's.
"""

import dataclasses
import functools
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["REPRO_PLAN_CACHE_DIR"] = "off"

import numpy as np  # noqa: E402

from repro.configs import get_reduced  # noqa: E402
from repro.kernels import attention as flash  # noqa: E402
from repro.launch.mesh import make_train_mesh  # noqa: E402
from repro.launch.steps import TrainStepConfig  # noqa: E402
from repro.launch.train import setup_train_step, train  # noqa: E402

SEQ = 512  # four tiles of 128: the kernel takes it


def main():
    schedule, mode = sys.argv[1], sys.argv[2]
    pipe, tp, hk = (int(a) for a in sys.argv[3:6])
    cfg = dataclasses.replace(
        get_reduced("internlm2_1_8b"), d_model=256, n_heads=4, n_kv_heads=hk,
        head_dim=128, d_ff=64,
    )
    mesh, binding = make_train_mesh(pipe, tp)
    kw = dict(binding=binding, microbatch=1, seq_len=SEQ, m=2 * pipe,
              tcfg=TrainStepConfig(executor_mode=mode))

    def flash_ops():  # ops named under the kernel's scope
        text = setup_train_step(cfg, schedule, mesh, **kw).lower().as_text(debug_info=True)
        return text.count("/flash/")

    def step():
        run = train(cfg, schedule, mesh, 1, **kw)
        return run.losses[0], run.grad_norms[0]

    assert not flash_ops(), "the jnp path took the kernel"
    want = step()
    flash.PLATFORM = "cpu"
    flash.causal_attention = functools.partial(flash.causal_attention, interpret=True)
    assert flash_ops(), "the kernel path did not take the kernel"
    got = step()
    np.testing.assert_allclose(got, want, rtol=2e-5)
    print(f"OK loss {got[0]:.7f} / {want[0]:.7f}, grad norm {got[1]:.7f} / {want[1]:.7f}")


if __name__ == "__main__":
    main()
