"""The train step names its parts with ``jax.named_scope``: F, B, W (the
executor's passes), head_ce (the LM head and loss inside them), attn (q, k,
v to the attention output), send (the pipe's permutes) and opt (the
optimizer tail).  The names reach the compiled ops' ``op_name``, where a
profile of the chip finds them, in every executor mode; the backward
passes keep the scopes set inside the model (``attn`` under ``B``)."""

import os
import re
import subprocess
import sys

import pytest

from repro.configs import get_reduced
from repro.launch.mesh import make_train_mesh
from repro.launch.steps import TrainStepConfig
from repro.launch.train import setup_train_step

SCOPES = ("F", "B", "W", "opt", "head_ce", "attn", "send")
OP_NAME = re.compile(r'op_name="([^"]*)"')
WRAPPED = re.compile(r"\w+\((.*)\)")  # jvp(attn), transpose(jvp(attn))


def _unwrap(part):
    m = WRAPPED.fullmatch(part)
    while m:
        part = m.group(1)
        m = WRAPPED.fullmatch(part)
    return part


def _scope_paths(op_names):
    """Each op's scopes, outermost first: ``.../B/transpose(jvp(attn))/dot``
    -> ("B", "attn")."""
    return [
        tuple(p for p in map(_unwrap, name.split("/")) if p in SCOPES)
        for name in op_names
    ]


@pytest.mark.parametrize(
    "mode,seq",
    [("scan", 64), ("unroll", 64), ("specialized", 64), ("specialized", 4096)],
    ids=["scan", "unroll", "specialized", "specialized-chunked-attn"],
)
def test_step_ops_carry_scopes(mode, seq):
    mesh, binding = make_train_mesh(1)
    setup = setup_train_step(
        get_reduced("internlm2_1_8b"), "1f1b", mesh, binding=binding,
        microbatch=1, seq_len=seq, m=2, tcfg=TrainStepConfig(executor_mode=mode),
    )
    names = OP_NAME.findall(setup.lower().compile().as_text())
    paths = set(_scope_paths(names))
    for outer in ("F", "B", "W", "opt"):
        assert any(p[:1] == (outer,) for p in paths), f"no op under {outer}"
    # the head sits inside each pass; attention (no weights) in F and B
    for inner in (("F", "head_ce"), ("B", "head_ce"), ("W", "head_ce"), ("F", "attn"), ("B", "attn")):
        assert inner in paths, f"no op under {'/'.join(inner)}: {sorted(paths)}"
    # the passes never nest, and nothing is under send without a pipe
    assert not any(len(set(p) & {"F", "B", "W", "opt"}) > 1 for p in paths)
    assert not any("send" in p for p in paths)
    # B and W ops are bound under the split equations' own name stacks
    # (core/passes.py _eval_eqns): the transpose(...) that JAX's
    # differentiation put there survives under W
    w_ops = [n for n, p in zip(names, _scope_paths(names)) if p[:1] == ("W",)]
    assert any("/transpose(" in n for n in w_ops), w_ops[:5]


def test_attention_kernel_ops_are_under_attn_flash(flash_on_cpu):
    """With causal attention on the blocked kernel (as on the TPU; here the
    Pallas interpreter), the kernel's forward and its backward ops lie under
    ``attn/flash`` inside F and B: ``attn_ms`` keeps counting the backward,
    and the ops under ``attn/flash`` are the kernel's share of it."""
    import dataclasses

    cfg = dataclasses.replace(
        get_reduced("internlm2_1_8b"), d_model=256, n_heads=2, n_kv_heads=1, d_ff=64,
    )
    mesh, binding = make_train_mesh(1)
    setup = setup_train_step(
        cfg, "1f1b", mesh, binding=binding, microbatch=1, seq_len=512, m=2,
        tcfg=TrainStepConfig(executor_mode="specialized"),
    )
    names = OP_NAME.findall(setup.lower().compile().as_text())
    flash = [n for n in names if "/flash/" in n]
    paths = set(_scope_paths(flash))
    assert paths == {("F", "attn"), ("B", "attn")}, sorted(paths)
    for name in flash:
        parts = [_unwrap(p) for p in name.split("/")]
        assert parts[parts.index("attn") + 1] == "flash", name
    # the forward kernel in F, its backward kernels (dq; dk, dv) in B
    for outer, kernel in (("F", "fwd"), ("B", "dq"), ("B", "dkv")):
        assert any(
            f"/{outer}/" in n and f"/flash/flash_{kernel}/" in n for n in flash
        ), (outer, kernel)


@pytest.mark.parametrize("schedule,mode", [("zb-v", "scan"), ("1f1b", "specialized")])
def test_pipe_permutes_are_under_send(schedule, mode):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    script = os.path.join(os.path.dirname(__file__), "spmd_scripts", "send_scopes.py")
    out = subprocess.run(
        [sys.executable, script, schedule, mode],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_split_scan_halves_keep_the_scan_scope():
    """A scan with weights in its body splits into a B half and a W half
    (``core/passes.py`` ``_SynthScanEqn``); both are bound under the name
    stack of the scan they replace, so a scope around the scan stays on
    the backward scans' ops."""
    import jax
    import jax.numpy as jnp

    from repro.core.passes import _SynthScanEqn, auto_fbw

    def rnn(params, x, side):
        def step(h, xt):
            h2 = jnp.tanh(xt @ params["W"] + h @ params["U"])
            return h2, h2

        with jax.named_scope("attn"):
            h0 = jnp.zeros((x.shape[0], params["W"].shape[1]))
            _, hs = jax.lax.scan(step, h0, x.transpose(1, 0, 2))
        return hs.transpose(1, 0, 2) @ params["out"]

    key = jax.random.PRNGKey(0)
    params = {
        "W": jax.random.normal(key, (5, 4)),
        "U": jax.random.normal(jax.random.fold_in(key, 1), (4, 4)),
        "out": jax.random.normal(jax.random.fold_in(key, 2), (4, 3)),
    }
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, 6, 5))
    mod = auto_fbw(rnn, name="rnn", compact=True)
    y, res = mod.fwd(params, x, {})

    def while_scopes(fn, *args):
        names = OP_NAME.findall(jax.jit(fn).lower(*args).compile().as_text())
        return {p for n, p in zip(names, _scope_paths(names)) if "/while" in n}

    dy = jnp.ones_like(y)
    b = while_scopes(lambda p, r, g: mod.bwd_x(p, r, g, {}), params, res, dy)
    _, wctx = mod.bwd_x(params, res, dy, {})
    assert any(isinstance(e, _SynthScanEqn) for e in mod._split.jaxpr.eqns)
    w = while_scopes(lambda p, c: mod.bwd_w(p, c, {}), params, wctx)
    assert b == {("attn",)} and w == {("attn",)}, (b, w)
