"""Split-backward numerical parity per block kind (ISSUE 2 satellite),
plus the recurrent B/W split acceptance (ISSUE 4).

For every layer kind reachable from the dry-run shape grid
(configs/shapes.py enumerates ARCH_IDS; their block patterns cover the kinds
tested here), the dgrad/wgrad pair produced by the backward-jaxpr partition
(core/passes.auto_fbw) must reproduce the fused ``jax.vjp`` gradients:
``bwd_x`` returns the same dx, and ``bwd_w`` -- from the compact M_W context
alone, residuals freed -- the same parameter grads.  The loss/head sink path
(final norm + vocab-parallel CE) is covered too, as is the fused
``acc``-routing through kernels/wgrad_accum.

ISSUE 4 additions: parity holds through the *compact* partition (wrapper
inlining + byte-minimal cut + recursive scan split) for every kind, for
both RG-LRU recurrence forms, and for a weights-inside-scan RNN whose
per-step wgrad GEMMs must move into the W scan; the measured per-block
W-context bytes of the recurrent configs shrink >= 30% vs. the
whole-scan-in-B frontier baseline (``compact=False``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.core.passes import _SynthScanEqn, auto_fbw
from repro.models.lm import ArchConfig, make_sink_fn
from repro.models.modules import ShardCtx, apply_block, apply_layer, init_layer

jax.config.update("jax_enable_x64", False)

# tolerances per dtype: fp32 kinds are tight; bf16 params lose ~8 bits
TOL = {"float32": dict(rtol=2e-5, atol=2e-5), "bfloat16": dict(rtol=2e-2, atol=2e-2)}

BASE = dict(
    d_model=16, n_heads=4, n_kv_heads=2, d_ff=32, n_layers=2, head_dim=4,
    tp_size=1,
)

# one tiny config per kind; every kind used by the shape-grid archs appears.
# "<kind>:<tag>" entries are extra variants of the same layer kind (the
# scanified RG-LRU fallback routes the recurrence through lax.scan).
KIND_CFG = {
    "attn": dict(BASE),
    "attn_local": dict(BASE, window=4),
    "mlp": dict(BASE),
    "mla": dict(BASE, q_lora_rank=8, kv_lora_rank=8, qk_rope_head_dim=4),
    "moe": dict(BASE, n_experts=4, topk=2, moe_d_ff=16, n_shared_experts=1,
                capacity=8),
    "slstm": dict(BASE),
    "mlstm": dict(BASE),
    "rglru": dict(BASE, lru_width=16),
    "rglru:seq": dict(BASE, lru_width=16, rglru_scan="sequential"),
    "encdec": dict(BASE, s_enc=4),
}


def test_kind_coverage_matches_shape_grid():
    """Every block kind in the configs/shapes.py grid has a parity case."""
    grid_kinds = {
        k
        for arch in ARCH_IDS
        for kinds in get_config(arch).block_pattern
        for k in kinds
    }
    assert grid_kinds <= set(KIND_CFG), sorted(grid_kinds - set(KIND_CFG))


def _block_case(kind, dtype):
    lcfg = KIND_CFG[kind]
    layer_kind = kind.split(":")[0]
    ctx = ShardCtx()
    key = jax.random.PRNGKey(0)
    params = init_layer(layer_kind, key, lcfg, ctx, dtype)
    b, s = 2, 8
    s_total = s + (lcfg["s_enc"] if layer_kind == "encdec" else 0)
    x = (jax.random.normal(jax.random.PRNGKey(1), (b, s_total, lcfg["d_model"]))
         * 0.5).astype(dtype)
    side = {"positions": jnp.arange(s_total)}

    def f(p, xx, sd):
        return apply_layer(layer_kind, p, xx, sd["positions"], lcfg, ctx)

    return f, params, x, side


@pytest.mark.parametrize("kind", sorted(KIND_CFG))
def test_split_backward_parity(kind):
    dtype = jnp.float32
    f, params, x, side = _block_case(kind, dtype)
    mod = auto_fbw(f, name=kind)
    y, res = mod.fwd(params, x, side)
    dy = (jax.random.normal(jax.random.PRNGKey(2), y.shape) * 0.5).astype(y.dtype)

    dx, wctx = mod.bwd_x(params, res, dy, side)
    grads = mod.bwd_w(params, wctx, side)

    ref_grads, ref_dx = jax.vjp(lambda p, xx: f(p, xx, side), params, x)[1](dy)
    tol = TOL["float32"]
    np.testing.assert_allclose(dx, ref_dx, **tol)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    flat_ref = jax.tree_util.tree_leaves(ref_grads)
    for (path, g), rg in zip(flat, flat_ref):
        np.testing.assert_allclose(
            g, rg, err_msg=f"{kind}: wgrad mismatch at {jax.tree_util.keystr(path)}",
            **tol,
        )


def test_split_backward_parity_bf16():
    """Dtype-sensitive path: bf16 params, per-dtype tolerance."""
    f, params, x, side = _block_case("mlp", jnp.bfloat16)
    mod = auto_fbw(f, name="mlp_bf16")
    y, res = mod.fwd(params, x, side)
    dy = jnp.ones_like(y)
    dx, wctx = mod.bwd_x(params, res, dy, side)
    grads = mod.bwd_w(params, wctx, side)
    ref_grads, ref_dx = jax.vjp(lambda p, xx: f(p, xx, side), params, x)[1](dy)
    tol = TOL["bfloat16"]
    np.testing.assert_allclose(
        dx.astype(np.float32), ref_dx.astype(np.float32), **tol
    )
    for g, rg in zip(
        jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(ref_grads)
    ):
        np.testing.assert_allclose(
            g.astype(np.float32), rg.astype(np.float32), **tol
        )


def test_sink_split_parity():
    """Loss/head sink: final norm + vocab-parallel CE, split B/W vs vjp."""
    cfg = ArchConfig(
        name="sink_tiny", family="dense", n_layers=2, d_model=16, n_heads=2,
        n_kv_heads=2, d_ff=32, vocab=64,
    )
    ctx = ShardCtx()
    m = 4
    sink_fn = make_sink_fn(cfg, ctx, m)
    key = jax.random.PRNGKey(3)
    shared = {
        "embed": jax.random.normal(key, (64, 16)) * 0.02,
        "head": jax.random.normal(jax.random.fold_in(key, 1), (16, 64)) * 0.02,
        "final_ln": jnp.zeros((16,)),
    }
    b, s = 2, 8
    y = jax.random.normal(jax.random.fold_in(key, 2), (b, s, 16))
    side = {
        "labels": jax.random.randint(jax.random.fold_in(key, 3), (b, s), 0, 64),
        "positions": jnp.arange(s),
        "tokens": jax.random.randint(jax.random.fold_in(key, 4), (b, s), 0, 64),
    }
    mod = auto_fbw(sink_fn, name="sink")
    loss, res = mod.fwd(shared, y, side)
    ones = jnp.ones_like(loss)
    dy, wctx = mod.bwd_x(shared, res, ones, side)
    grads = mod.bwd_w(shared, wctx, side)
    ref_grads, ref_dy = jax.vjp(lambda sh, yy: sink_fn(sh, yy, side), shared, y)[
        1
    ](ones)
    tol = TOL["float32"]
    np.testing.assert_allclose(dy, ref_dy, **tol)
    for k in shared:
        np.testing.assert_allclose(
            grads[k], ref_grads[k], err_msg=f"sink grad {k}", **tol
        )


def test_wgrad_acc_fusion_routes_through_kernel():
    """bwd_w(acc=...) returns acc + grads, fusing terminal dW = a^T @ g
    outer products through kernels/wgrad_accum (fp32 accumulators only)."""
    f, params, x, side = _block_case("mlp", jnp.float32)
    mod = auto_fbw(f, name="mlp_acc")
    y, res = mod.fwd(params, x, side)
    dy = jnp.ones_like(y)
    _, wctx = mod.bwd_x(params, res, dy, side)
    grads = mod.bwd_w(params, wctx, side)
    acc = jax.tree_util.tree_map(
        lambda l: jnp.full(l.shape, 0.5, jnp.float32), params
    )
    fused = mod.bwd_w(params, wctx, side, acc=acc)
    plan = mod._split
    assert any(r is not None for r in plan.wgrad_routes), (
        "no dW = a^T @ g route matched for the MLP block"
    )
    for g, fg in zip(
        jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(fused)
    ):
        np.testing.assert_allclose(fg, 0.5 + g, rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------- #
# ISSUE 4: the recurrent B/W split + byte-minimal W-contexts
# --------------------------------------------------------------------- #
def _tree_bytes(t):
    return sum(
        int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(t)
    )


def _block_split_case(kinds, lcfg):
    ctx = ShardCtx()
    kp = tuple(
        init_layer(k, jax.random.fold_in(jax.random.PRNGKey(0), i), lcfg, ctx,
                   jnp.float32)
        for i, k in enumerate(kinds)
    )
    params = (jnp.ones((), jnp.float32), kp)
    b, s = 2, 8
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, lcfg["d_model"])) * 0.5
    side = {"positions": jnp.arange(s)}

    def f(p, xx, sd):
        mask, bp = p
        return apply_block(kinds, mask, bp, xx, sd["positions"], lcfg, ctx)

    return f, params, x, side


@pytest.mark.parametrize(
    "kinds",
    [("slstm",), ("mlstm",), ("rglru", "mlp")],
    ids=lambda k: "+".join(k),
)
def test_compact_context_shrinks_recurrent_blocks(kinds):
    """ISSUE 4 acceptance core: >= 30% smaller M_W per recurrent block vs.
    the whole-scan-in-B frontier baseline, with exact grad parity between
    the two partitions."""
    lcfg = dict(BASE)
    if "rglru" in kinds:
        lcfg["lru_width"] = 16
    f, params, x, side = _block_split_case(kinds, lcfg)
    dy = (jax.random.normal(jax.random.PRNGKey(2), x.shape) * 0.5).astype(
        x.dtype
    )
    got = {}
    for compact in (False, True):
        mod = auto_fbw(f, name=f"{kinds}-{compact}", compact=compact)
        y, res = mod.fwd(params, x, side)
        dx, wctx = mod.bwd_x(params, res, dy, side)
        grads = mod.bwd_w(params, wctx, side)
        got[compact] = (_tree_bytes(wctx), dx, grads)
    base_bytes, dx0, g0 = got[False]
    compact_bytes, dx1, g1 = got[True]
    assert compact_bytes <= 0.70 * base_bytes, (
        f"{kinds}: compact W-context {compact_bytes}B > 70% of the "
        f"whole-scan-in-B baseline {base_bytes}B"
    )
    tol = TOL["float32"]
    np.testing.assert_allclose(dx1, dx0, **tol)
    for a, b_ in zip(
        jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)
    ):
        np.testing.assert_allclose(a, b_, **tol)


def _rnn_case():
    """A true RNN: weights used *inside* the scan body, so the backward
    scan accumulates dW as a carry whose final value is dp-only."""

    def rnn(params, x, side):
        W, U, out = params["W"], params["U"], params["out"]

        def step(h, xt):
            h2 = jnp.tanh(xt @ W + h @ U)
            return h2, h2

        h0 = jnp.zeros((x.shape[0], W.shape[1]))
        _, hs = jax.lax.scan(step, h0, x.transpose(1, 0, 2))
        return hs.transpose(1, 0, 2) @ out

    key = jax.random.PRNGKey(0)
    params = {
        "W": jax.random.normal(key, (5, 4)) * 0.3,
        "U": jax.random.normal(jax.random.fold_in(key, 1), (4, 4)) * 0.3,
        "out": jax.random.normal(jax.random.fold_in(key, 2), (4, 3)) * 0.3,
    }
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, 6, 5))
    return rnn, params, x


def test_scan_split_moves_wgrad_gemms_to_w():
    """Weights-inside-scan: the body partition must split the backward scan
    into a dx-only B scan and a W replay scan that owns the per-step wgrad
    GEMMs and the dW accumulator carries -- with full grad parity."""
    rnn, params, x = _rnn_case()
    mod = auto_fbw(rnn, name="rnn", compact=True)
    y, res = mod.fwd(params, x, {})
    dy = jax.random.normal(jax.random.PRNGKey(9), y.shape)
    dx, wctx = mod.bwd_x(params, res, dy, {})
    grads = mod.bwd_w(params, wctx, {})
    ref_g, ref_dx = jax.vjp(lambda p, xx: rnn(p, xx, {}), params, x)[1](dy)
    tol = TOL["float32"]
    np.testing.assert_allclose(dx, ref_dx, **tol)
    for k in params:
        np.testing.assert_allclose(grads[k], ref_g[k], err_msg=k, **tol)

    plan = mod._split
    halves = {
        e.primitive.name: e
        for e in plan.jaxpr.eqns
        if isinstance(e, _SynthScanEqn)
    }
    assert set(halves) == {"scan_b", "scan_w"}, sorted(halves)

    def body_dots(e):
        return sum(
            1
            for i in e.body_eqn_ids
            if e.body.eqns[i].primitive.name == "dot_general"
        )

    # W-x-grad GEMMs (xt@W, h@U transposes) stay in B; the per-step
    # dW = a^T g GEMMs for W and U run in the W replay scan
    assert body_dots(halves["scan_b"]) == 2
    assert body_dots(halves["scan_w"]) == 2
    # the dW accumulators ride the W scan as carries (2 of them: W and U)
    w_half = halves["scan_w"]
    assert len(w_half.invars) >= 2
    # and the B scan emits a per-step stacked context for the replay
    assert w_half.n_ctx >= 1

    # the poisoning property holds through the scan split too
    del res
    grads2 = mod.bwd_w(params, wctx, {})
    for a, b_ in zip(
        jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads2)
    ):
        np.testing.assert_array_equal(a, b_)


def test_scan_split_elementwise_weight_accumulator_parity():
    """Elementwise weight inside the scan body: the backward accumulates
    its grad as a param-shaped W-carry.  The body cut must never select a
    value computed *from* that carry (it exists only at W time), even when
    it is the byte-cheapest node on the chain -- regression for the
    W-carry availability hole in the body min-cut."""

    def f(params, x, side):
        u, out = params["u"], params["out"]

        def step(h, xt):
            h2 = jnp.tanh(xt + h * u)
            return h2, h2

        h0 = jnp.zeros((x.shape[0], x.shape[2]))
        _, hs = jax.lax.scan(step, h0, x.transpose(1, 0, 2))
        return hs.transpose(1, 0, 2) @ out

    key = jax.random.PRNGKey(4)
    params = {
        "u": jax.random.normal(key, (5,)) * 0.3,
        "out": jax.random.normal(jax.random.fold_in(key, 1), (5, 3)) * 0.3,
    }
    x = jax.random.normal(jax.random.fold_in(key, 2), (3, 7, 5))
    mod = auto_fbw(f, name="ew_rnn", compact=True)
    y, res = mod.fwd(params, x, {})
    dy = jax.random.normal(jax.random.fold_in(key, 3), y.shape)
    dx, wctx = mod.bwd_x(params, res, dy, {})
    grads = mod.bwd_w(params, wctx, {})
    ref_g, ref_dx = jax.vjp(lambda p, xx: f(p, xx, {}), params, x)[1](dy)
    tol = TOL["float32"]
    np.testing.assert_allclose(dx, ref_dx, **tol)
    for k in params:
        np.testing.assert_allclose(grads[k], ref_g[k], err_msg=k, **tol)


def test_dp_only_scan_runs_in_w():
    """A scan feeding only dparams must run wholly at W time: its equation
    (or synthetic replacement) sits in the W slice, none of it in B."""

    # dx = (1 + sum(c)) needs only the scan's *forward* value (a stored
    # residual); the dparams["gs"] pullback is a transposed scan that only
    # the W slice needs
    params = {
        "gs": jax.random.normal(jax.random.PRNGKey(0), (5, 4)) * 0.5,
    }
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3))

    def g(params, x, side):
        def step(c, gt):
            return c * 0.9 + jnp.tanh(gt), None
        c, _ = jax.lax.scan(step, jnp.zeros((4,)), params["gs"])
        return x * (1.0 + jnp.sum(c))

    mod = auto_fbw(g, name="dponly", compact=True)
    y, res = mod.fwd(params, x, {})
    dy = jnp.ones_like(y)
    dx, wctx = mod.bwd_x(params, res, dy, {})
    grads = mod.bwd_w(params, wctx, {})
    ref_g, ref_dx = jax.vjp(lambda p, xx: g(p, xx, {}), params, x)[1](dy)
    tol = TOL["float32"]
    np.testing.assert_allclose(dx, ref_dx, **tol)
    np.testing.assert_allclose(grads["gs"], ref_g["gs"], **tol)

    plan = mod._split
    b_scans = [
        i
        for i in plan.b_eqns
        if isinstance(plan.jaxpr.eqns[i], _SynthScanEqn)
        or getattr(plan.jaxpr.eqns[i].primitive, "name", "") == "scan"
    ]
    w_scans = [
        i
        for i in plan.w_eqns
        if isinstance(plan.jaxpr.eqns[i], _SynthScanEqn)
        or getattr(plan.jaxpr.eqns[i].primitive, "name", "") == "scan"
    ]
    assert not b_scans, "dp-only backward scan leaked into the B slice"
    assert w_scans, "dp-only backward scan missing from the W slice"


def test_compat_env_flag_restores_frontier_cut(monkeypatch):
    """REPRO_SPLIT_COMPAT=1 falls back to the legacy frontier partition.

    No importlib.reload here: reloading would re-create the module's
    classes and break ``isinstance(..., _SynthScanEqn)`` checks in any
    test that runs afterwards.  The default is patched as a module attr
    (read at construction time); the env parsing is exercised in a clean
    subprocess.
    """
    import subprocess
    import sys

    import repro.core.passes as passes

    monkeypatch.setattr(passes, "_COMPACT_DEFAULT", False)
    mod = passes.auto_fbw(lambda p, x, sd: x * p, name="compat")
    assert mod.compact is False
    monkeypatch.setattr(passes, "_COMPACT_DEFAULT", True)
    assert passes.auto_fbw(lambda p, x, sd: x * p, name="c2").compact is True

    out = subprocess.run(
        [
            sys.executable,
            "-c",
            "import repro.core.passes as p; print(p._COMPACT_DEFAULT)",
        ],
        env={
            **__import__("os").environ,
            "REPRO_SPLIT_COMPAT": "1",
            "PYTHONPATH": "src",
        },
        capture_output=True,
        text=True,
        cwd=str(__import__("pathlib").Path(__file__).resolve().parent.parent),
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def test_residuals_not_needed_after_b():
    """The W pass must run from the M_W context alone: poisoning the
    residual buffers after B changes nothing (true split, no rebuild)."""
    f, params, x, side = _block_case("attn", jnp.float32)
    mod = auto_fbw(f, name="attn_poison")
    y, res = mod.fwd(params, x, side)
    dy = jnp.ones_like(y)
    _, wctx = mod.bwd_x(params, res, dy, side)
    grads = mod.bwd_w(params, wctx, side)
    del res  # freed at B in the executor; bwd_w cannot touch it by design
    grads2 = mod.bwd_w(params, wctx, side)
    for a, b_ in zip(
        jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads2)
    ):
        np.testing.assert_array_equal(a, b_)


def test_split_parity_through_the_attention_kernel(flash_on_cpu):
    """The q/k/v/o projections around the blocked attention kernel (its
    custom VJP brings Pallas backward kernels): B and W from the split
    reproduce ``jax.vjp``'s dx and weight gradients, and every kernel call
    of the backward lies in the B slice -- attention has no weights, so W
    holds only the projections' weight GEMMs."""
    lcfg = dict(d_model=256, n_heads=2, n_kv_heads=1, head_dim=128, d_ff=32,
                n_layers=2, tp_size=1)
    ctx = ShardCtx()
    params = init_layer("attn", jax.random.PRNGKey(0), lcfg, ctx, jnp.float32)
    s = 512
    x = jax.random.normal(jax.random.PRNGKey(1), (1, s, 256)) * 0.5
    side = {"positions": jnp.arange(s)}

    def f(p, xx, sd):
        return apply_layer("attn", p, xx, sd["positions"], lcfg, ctx)

    mod = auto_fbw(f, name="attn_flash")
    y, res = mod.fwd(params, x, side)
    dy = jax.random.normal(jax.random.PRNGKey(2), y.shape) * 0.5
    dx, wctx = mod.bwd_x(params, res, dy, side)
    grads = mod.bwd_w(params, wctx, side)
    ref_grads, ref_dx = jax.vjp(lambda p, xx: f(p, xx, side), params, x)[1](dy)
    tol = TOL["float32"]
    np.testing.assert_allclose(dx, ref_dx, **tol)
    for k in params:
        np.testing.assert_allclose(grads[k], ref_grads[k], err_msg=k, **tol)

    # every equation that holds a backward kernel is in B, none in W
    plan = mod._split
    kernels = [
        i for i, e in enumerate(plan.jaxpr.eqns) if "pallas_call" in str(e)
    ]
    assert kernels
    assert set(kernels) <= set(plan.b_eqns)
    assert not set(kernels) & set(plan.w_eqns)
