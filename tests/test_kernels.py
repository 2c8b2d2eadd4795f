"""Pallas kernel correctness: interpret-mode allclose vs ref.py oracles,
swept over shapes / dtypes / tilings (hypothesis for the invariants)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.ref import rmsnorm_ref, wgrad_accum_ref
from repro.kernels.rmsnorm import rmsnorm_fused
from repro.kernels.wgrad_accum import wgrad_accum
from repro.kernels import ops


def _rand(key, shape, dtype):
    return (jax.random.normal(key, shape) * 0.5).astype(dtype)


WGRAD_SHAPES = [
    # (n, h, f, bn, bh, bf)
    (256, 128, 128, 64, 64, 128),
    (512, 256, 128, 128, 128, 128),
    (128, 128, 512, 128, 128, 128),
    (1024, 128, 256, 512, 128, 128),
]


@pytest.mark.parametrize("n,h,f,bn,bh,bf", WGRAD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wgrad_accum_matches_ref(n, h, f, bn, bh, bf, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    a = _rand(ks[0], (n, h), dtype)
    g = _rand(ks[1], (n, f), dtype)
    acc = _rand(ks[2], (h, f), jnp.float32)
    out = wgrad_accum(a, g, acc, bh=bh, bf=bf, bn=bn, interpret=True)
    ref = wgrad_accum_ref(a, g, acc)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


@given(
    n_blocks=st.integers(1, 4),
    h_blocks=st.integers(1, 2),
    seed=st.integers(0, 20),
)
@settings(max_examples=10, deadline=None)
def test_wgrad_accum_property(n_blocks, h_blocks, seed):
    n, h, f = 64 * n_blocks, 64 * h_blocks, 128
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    a = _rand(ks[0], (n, h), jnp.float32)
    g = _rand(ks[1], (n, f), jnp.float32)
    acc = _rand(ks[2], (h, f), jnp.float32)
    out = wgrad_accum(a, g, acc, bh=64, bf=128, bn=64, interpret=True)
    np.testing.assert_allclose(
        out, wgrad_accum_ref(a, g, acc), rtol=2e-5, atol=2e-5
    )


RMS_SHAPES = [(256, 128, 64), (512, 1024, 256), (128, 384, 128)]


@pytest.mark.parametrize("n,h,br", RMS_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(n, h, br, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    x = _rand(ks[0], (n, h), dtype)
    g = _rand(ks[1], (h,), jnp.float32)
    out = rmsnorm_fused(x, g, br=br, interpret=True)
    ref = rmsnorm_ref(x, g)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_rmsnorm_custom_vjp_matches_autodiff():
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    x = _rand(ks[0], (32, 64), jnp.float32)
    g = _rand(ks[1], (64,), jnp.float32)

    def f_ops(x, g):
        return jnp.sum(ops.rmsnorm(x, g) ** 2)

    def f_ref(x, g):
        return jnp.sum(rmsnorm_ref(x, g) ** 2)

    dx1, dg1 = jax.grad(f_ops, argnums=(0, 1))(x, g)
    dx2, dg2 = jax.grad(f_ref, argnums=(0, 1))(x, g)
    np.testing.assert_allclose(dx1, dx2, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(dg1, dg2, rtol=1e-4, atol=1e-5)


def test_wgrad_hbm_traffic_savings():
    """The fusion claim: unfused = matmul out + add (2 extra acc-sized HBM
    round trips); verify against XLA's bytes-accessed estimate."""
    n, h, f = 512, 256, 256
    a = jnp.ones((n, h), jnp.bfloat16)
    g = jnp.ones((n, f), jnp.bfloat16)
    acc = jnp.ones((h, f), jnp.float32)

    def unfused(a, g, acc):
        return acc + jax.lax.dot_general(
            a, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    cost = jax.jit(unfused).lower(a, g, acc).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):  # one dict per device program
        cost = cost[0]
    # inputs + matmul-out write + add read + add write >= 3 acc-sized arrays
    assert cost["bytes accessed"] >= (a.size * 2 + g.size * 2 + 3 * acc.size * 4) * 0.9


# --------------------------------------------------------------------- #
# causal attention (kernels/attention.py) against the dense jnp path
# --------------------------------------------------------------------- #
def _qkv(b, s, hq, hk, dqk, dv=None, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    return (
        jax.random.normal(ks[0], (b, s, hq, dqk)).astype(dtype),
        jax.random.normal(ks[1], (b, s, hk, dqk)).astype(dtype),
        jax.random.normal(ks[2], (b, s, hk, dv or dqk)).astype(dtype),
    )


@pytest.mark.parametrize(
    "b,s,hq,hk,block", [(2, 512, 4, 2, 128), (1, 1024, 6, 2, 256)],
    ids=["gqa-4-2-tile-128", "gqa-6-2-tile-256"],
)
def test_flash_attention_matches_dense(b, s, hq, hk, block):
    """The kernel in the Pallas interpreter against ``_attend_dense`` in f32
    on the same bf16 inputs, output and dq, dk, dv, to bf16 rounding.  The
    sequences are cut into at least four tiles, so whole tiles above the
    diagonal are skipped, and q heads share kv heads in groups of 2 and 3."""
    from repro.kernels import attention as flash
    from repro.models.modules import _attend_dense

    q, k, v = _qkv(b, s, hq, hk, 128)
    assert flash.kernel_block(
        q.shape, k.shape, v.shape, causal=True, window=None, softcap=None,
        platform="tpu",
    ) == block

    def dense(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        k, v = jnp.repeat(k, hq // hk, axis=2), jnp.repeat(v, hq // hk, axis=2)
        with jax.default_matmul_precision("highest"):
            return _attend_dense(q, k, v, True, None, None)

    def kernel(q, k, v):
        return flash.causal_attention(q, k, v, block, interpret=True)

    dy = jax.random.normal(jax.random.PRNGKey(4), q.shape)
    out_ref, vjp_ref = jax.vjp(dense, q, k, v)
    out, vjp = jax.vjp(kernel, q, k, v)
    assert out.shape == q.shape and out.dtype == q.dtype
    got = (out,) + vjp(dy.astype(q.dtype))
    want = (out_ref,) + vjp_ref(dy)
    for name, a, b_ in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b_.shape, name
        a, b_ = np.asarray(a, np.float32), np.asarray(b_, np.float32)
        rel = np.linalg.norm(a - b_) / np.linalg.norm(b_)
        assert rel < 1e-2, (name, rel)


# (case, q shape (b, s, hq, d), kv heads, v head dim, attention kwargs,
#  platform, kernel tile or None for the jnp path)
FLASH_DISPATCH = [
    ("kernel", (1, 512, 4, 128), 2, 128, {}, "tpu", 128),
    ("cell", (1, 4096, 16, 128), 8, 128, {}, "tpu", 512),
    ("softcap", (1, 512, 4, 128), 2, 128, {"softcap": 50.0}, "tpu", None),
    ("window", (1, 512, 4, 128), 2, 128, {"window": 256}, "tpu", None),
    ("not-causal", (1, 512, 4, 128), 2, 128, {"causal": False}, "tpu", None),
    ("unequal-head-dims", (1, 512, 4, 192), 4, 128, {}, "tpu", None),
    ("head-dim-64", (1, 512, 4, 64), 2, 64, {}, "tpu", None),
    ("shorter-than-a-block", (1, 64, 4, 128), 2, 128, {}, "tpu", None),
    ("ungrouped-heads", (1, 512, 4, 128), 3, 128, {}, "tpu", None),
    ("cpu", (1, 512, 4, 128), 2, 128, {}, "cpu", None),
]


@pytest.mark.parametrize(
    "case,q_shape,hk,dv,kw,platform,block", FLASH_DISPATCH,
    ids=[c[0] for c in FLASH_DISPATCH],
)
def test_attention_dispatch(case, q_shape, hk, dv, kw, platform, block):
    """``attention()`` takes the kernel only on the TPU, for causal
    attention with no window or softcap, a shared head dim that is a
    multiple of 128, grouped kv heads and a sequence of whole tiles;
    everything else traces the jnp path it always had."""
    from repro.kernels import attention as flash
    from repro.models.modules import attention

    b, s, hq, d = q_shape
    q, k, v = _qkv(b, s, hq, hk, d, dv)
    args = dict(causal=True, window=None, softcap=None)
    args.update(kw)
    assert flash.kernel_block(q.shape, k.shape, v.shape, platform=platform, **args) == block
    if hq % hk:
        return  # no path takes ungrouped heads; apply_attn never builds them
    fn = lambda q, k, v: attention(q, k, v, platform=platform, **kw)
    jaxpr = str(jax.make_jaxpr(fn)(q, k, v))
    assert ("pallas_call" in jaxpr) == (block is not None), case
    if block is not None:  # kv reach the kernel heads-first at hk heads, not repeated
        assert f"bf16[{b},{hk},{s},{d}]" in jaxpr, case


# (schedule, executor mode, pipe, tp, kv heads): 4 q heads of 128
FLASH_MESHES = [
    ("zb-v", "scan", 4, 1, 1),
    ("1f1b", "specialized", 2, 2, 2),  # kv sharded with q: 2 q heads on 1 kv head
    ("1f1b", "scan", 1, 4, 2),  # kv replicated: each device selects its q head's
]


@pytest.mark.parametrize(
    "schedule,mode,pipe,tp,hk", FLASH_MESHES,
    ids=["zb-v-pipe4", "pipe2-tp2-kv-sharded", "tp4-kv-replicated"],
)
def test_flash_train_step_on_meshes(schedule, mode, pipe, tp, hk):
    """On four virtual devices, one train step with the kernel taken (the
    Pallas interpreter) gives the jnp path's loss and gradient norm."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    script = os.path.join(os.path.dirname(__file__), "spmd_scripts", "flash_parity.py")
    out = subprocess.run(
        [sys.executable, script, schedule, mode, str(pipe), str(tp), str(hk)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
