"""The Pallas kernels compile for a TPU v5e at the widths of the train path.

No chip is needed: the TPU compiler compiles for a described v5e:2x2
topology.  Each kernel must come out as a Mosaic ``tpu_custom_call`` -- a
kernel that the TPU compiler refuses (misaligned tiles, too much VMEM) fails
here, where the interpreter would have run it.

Widths are internlm2-1.8b's at seq 4096, microbatch 1: the W pass of the
MLP up-projection (4096 x 2048 . 4096 x 8192 bf16 into an fp32 2048 x 8192
accumulator), the pre-attention RMSNorm (4096 x 2048 bf16) and causal
attention (16 query / 8 KV heads of 128).
"""

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_wgrad_accum_compiles_for_v5e(one_chip):
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compiled_text(
        lambda a, g, acc: ops.wgrad_accum(a, g, acc, use_pallas=True),
        sd((4096, 2048), jnp.bfloat16),
        sd((4096, 8192), jnp.bfloat16),
        sd((2048, 8192), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_rmsnorm_fused_compiles_for_v5e(one_chip):
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compiled_text(
        lambda x, g: ops.rmsnorm(x, g, True),
        sd((4096, 2048), jnp.bfloat16),
        sd((2048,), jnp.bfloat16),
    )
    assert "tpu_custom_call" in text


def test_attention_kernel_compiles_for_v5e(one_chip):
    """Forward plus backward of the blocked attention kernel at the cell's
    widths: Mosaic kernels (forward, dq, dk/dv), and fewer compiled
    temporaries than the jnp path's per-block f32 scores."""
    from repro.kernels import attention as flash
    from repro.models.modules import attention

    sd = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    q, kv = sd((1, 4096, 16, 128)), sd((1, 4096, 8, 128))
    block = flash.kernel_block(
        q.shape, kv.shape, kv.shape, causal=True, window=None, softcap=None,
        platform="tpu",
    )

    def kernel(q, k, v, dy):
        out, vjp = jax.vjp(lambda *a: flash.causal_attention(*a, block), q, k, v)
        return out, vjp(dy)

    def jnp_path(q, k, v, dy):
        out, vjp = jax.vjp(functools.partial(attention, platform="cpu"), q, k, v)
        return out, vjp(dy)

    def compile_(fn):
        return jax.jit(fn).lower(q, kv, kv, q).compile()

    fast, slow = compile_(kernel), compile_(jnp_path)
    assert fast.as_text().count('custom_call_target="tpu_custom_call"') == 3
    assert "tpu_custom_call" not in slow.as_text()
    fast_tmp = fast.memory_analysis().temp_size_in_bytes
    slow_tmp = slow.memory_analysis().temp_size_in_bytes
    assert fast_tmp < slow_tmp, (fast_tmp, slow_tmp)


# one instruction a line, with its op_name: the form a profile's reader
# maps device ops to the step's scopes in
INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"', re.M)


def _unwrap(part):
    """``transpose(jvp(attn))`` -> ``attn``."""
    while (m := re.fullmatch(r"\w+\((.*)\)", part)):
        part = m.group(1)
    return part


def test_attention_kernels_of_the_step_carry_their_scopes(one_chip):
    """The train step built for a v5e mesh takes the kernel (the platform
    comes from the mesh's devices, not from this CPU process), and each of
    its Mosaic calls prints as one instruction line whose ``op_name`` puts
    the forward under ``F/attn/flash`` and dq, dk/dv under ``B/attn/flash``."""
    from repro.configs import get_reduced
    from repro.launch.mesh import make_train_mesh
    from repro.launch.steps import TrainStepConfig
    from repro.launch.train import setup_train_step

    cfg = dataclasses.replace(
        get_reduced("internlm2_1_8b"), d_model=256, n_heads=2, n_kv_heads=1,
        head_dim=128, d_ff=256, dtype="bfloat16",
    )
    mesh, binding = make_train_mesh(1, devices=[one_chip._device])
    setup = setup_train_step(
        cfg, "1f1b", mesh, binding=binding, microbatch=1, seq_len=512, m=2,
        tcfg=TrainStepConfig(executor_mode="specialized"),
    )
    text = setup.lower().compile().as_text()
    calls = re.findall(
        r'^\s*(?:ROOT )?%?([\w.\-]+) = .*custom_call_target="tpu_custom_call"', text, re.M
    )
    assert calls and all(c.startswith("flash_") for c in calls), calls
    scopes = {}
    for name, op_name in INSTR.findall(text):
        if name in calls:
            parts = [_unwrap(p) for p in op_name.split("/")]
            scopes[name] = "/".join(p for p in parts if p in ("F", "B", "W", "attn", "flash"))
    assert set(scopes) == set(calls), sorted(set(calls) - set(scopes))
    kinds = {(n.split(".")[0], path) for n, path in scopes.items()}
    assert kinds == {
        ("flash_fwd", "F/attn/flash"), ("flash_dq", "B/attn/flash"),
        ("flash_dkv", "B/attn/flash"),
    }, sorted(kinds)
