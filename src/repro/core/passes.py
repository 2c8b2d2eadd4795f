"""F/B/W split machinery -- the paper's enabling primitive (Sec. 1, Fig. 1).

Every pipeline-stage computation is an :class:`FBWModule` with three passes:

  * ``fwd(params, x, side)   -> (y, res)``        -- forward, saving residuals
  * ``bwd_x(params, res, dy, side) -> (dx, wctx)`` -- input gradient (B)
  * ``bwd_w(params, wctx, side)    -> grads``      -- parameter gradient (W)

``B`` carries the inter-stage dependency chain; ``W`` is free to be scheduled
any time after its ``B`` on the same stage -- exactly the degree of freedom
the zero-bubble schedules exploit.

:func:`auto_fbw` derives a *true* split for any JAX function by partitioning
the backward jaxpr (no rematerialization, no pullback rebuild at W):

  1. ``fwd`` runs ``jax.vjp`` once; the pullback closure is a pytree
     (``jax.tree_util.Partial``), so its residuals are extracted by
     ``tree_flatten`` and stored in pipeline buffers.  Leaves that are merely
     forwarded parameter / side-input tracers are detected by object identity
     and *not* stored -- they are re-injected from the stage's own
     params/side at B/W time.
  2. On the first backward trace, the full pullback application
     ``(params, side, res, dy) -> (dparams, dx)`` is staged to a jaxpr,
     wrapper equations (``pjit`` / ``remat2`` / ``custom_vjp``) are inlined,
     and the flat program is partitioned: an equation belongs to the
     **B slice** iff its outputs are (transitively) needed for ``dx``; the
     equations needed for ``dparams`` form the **W slice**.  The values
     crossing the cut are the paper's ``M_W`` context.
  3. ``bwd_x`` evaluates only the B slice and returns ``(dx, wctx)`` where
     ``wctx`` is the tuple of cut values.  The F->B residuals are dead after
     this point: the executor frees their slot at B.
  4. ``bwd_w`` evaluates only the W slice from ``wctx`` plus re-injected
     params/side.  The wgrad GEMMs are never duplicated and the residuals
     are gone.

The context is not the naive B/W frontier: it is chosen *byte-minimal* by a
vertex min-cut over the backward dataflow (DESIGN.md Sec. 7).  Cheap
(elementwise / shape / reduction) equations may be replayed on the W side
from smaller stored precursors, and dparam cones made entirely of cheap ops
(mask grads, norm-gain grads, gate-scale grads) collapse to their finished
-- parameter-sized -- results computed at B.  GEMMs are pinned: a
``dot_general`` is never moved between slices, so the paper's Table-1 FLOP
split (B and W each carry one of the two backward matmuls per forward
matmul) is preserved exactly.  ``compact=False`` restores the frontier cut.

``scan`` equations are partitioned *recursively* (the recurrent B/W split):
a backward scan whose outputs feed both dx and dparams is split inside its
body.  B runs a dx-only scan that additionally emits a per-step compact
context as stacked outputs; W replays the dparam slice of the body as a
lightweight scan over that stacked context (dp-only accumulator carries --
e.g. a dW accumulated across steps -- move wholesale into the W scan).
Scans needed only for dparams run entirely in W with their unused inputs
pruned.  The recurrence's own residuals are therefore dead at B.

``bwd_w`` optionally takes a gradient accumulator; terminal ``dW = a^T @ g``
outer products are then routed through the fused accumulation kernel
(:func:`repro.kernels.ops.wgrad_accum`, paper App. A) when dtypes allow.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax.extend import core as _jcore
from jax.extend import source_info_util as _src_info

_Var = _jcore.Var
_Literal = _jcore.Literal
_DropVar = jax.core.DropVar

__all__ = ["FBWModule", "auto_fbw", "SequentialFBW", "loss_seed"]

PyTree = Any

#: default for auto_fbw(compact=...): byte-minimal W-contexts with cheap
#: replay + recursive scan split.  REPRO_SPLIT_COMPAT=1 restores the legacy
#: frontier cut globally (escape hatch; also the baseline tests measure
#: against).
_COMPACT_DEFAULT = os.environ.get("REPRO_SPLIT_COMPAT", "0") not in (
    "1",
    "on",
    "true",
)


class FBWModule:
    """Protocol + base class for split-backward modules."""

    #: set by subclasses / factories
    name: str = "fbw"

    def init(self, key: jax.Array) -> PyTree:
        raise NotImplementedError

    def fwd(self, params: PyTree, x: PyTree, side: PyTree) -> Tuple[PyTree, PyTree]:
        raise NotImplementedError

    def bwd_x(
        self, params: PyTree, res: PyTree, dy: PyTree, side: PyTree
    ) -> Tuple[PyTree, PyTree]:
        raise NotImplementedError

    def bwd_w(
        self, params: PyTree, wctx: PyTree, side: PyTree, acc: Optional[PyTree] = None
    ) -> PyTree:
        """Parameter gradients from the B pass's wctx alone (the paper's
        M_W context).  The F->B residuals are *not* available: they are
        freed when B completes.  When ``acc`` (a pytree matching params) is
        given, returns ``acc + grads`` with terminal outer products fused
        through the wgrad-accumulation kernel where dtypes allow."""
        raise NotImplementedError

    # convenience: fused backward for parity testing against jax.grad
    def bwd_full(self, params, res, dy, side):
        dx, wctx = self.bwd_x(params, res, dy, side)
        return dx, self.bwd_w(params, wctx, side)


# --------------------------------------------------------------------- #
# automatic split
# --------------------------------------------------------------------- #
_STORE, _PARAM, _SIDE = 0, 1, 2


@dataclasses.dataclass
class _SplitPlan:
    """Static partition of one backward jaxpr into B / W slices."""

    jaxpr: Any  # jax core Jaxpr
    consts: List[Any]
    b_eqns: List[int]
    w_eqns: List[int]
    cut_vars: List[Any]  # values riding the M_W context, in capture order
    reinject: Dict[Any, int]  # var -> flat (params+side) leaf index
    dp_vars: List[Any]
    dx_vars: List[Any]
    dp_tree: Any
    dx_tree: Any
    n_p: int
    n_s: int
    # dp leaf -> ("fuse", a_var, g_var, {eqn ids to skip}) | None
    wgrad_routes: List[Optional[Tuple]]
    key: Tuple


def _avals_key(*trees):
    return tuple(
        (tuple(l.shape), jnp.result_type(l).name)
        for l in jax.tree_util.tree_leaves(trees)
    )


def _eval_eqns(jaxpr, eqn_ids, env, skip=()):
    """Bind ``jaxpr.eqns[eqn_ids]`` in order, each under its own name stack
    stacked on the caller's (as ``jax.core.eval_jaxpr`` does), so the scopes
    set inside the model (``attn``) stay on the B and W ops."""
    for i in eqn_ids:
        if i in skip:
            continue
        eqn = jaxpr.eqns[i]
        invals = [
            v.val if isinstance(v, _Literal) else env[v] for v in eqn.invars
        ]
        info = eqn.source_info
        with _src_info.user_context(
            info.traceback,
            name_stack=_src_info.current_name_stack() + info.name_stack,
        ):
            if isinstance(eqn, _SynthScanEqn):
                outs = eqn.run(invals)
            else:
                ans = eqn.primitive.bind(*invals, **eqn.params)
                outs = ans if eqn.primitive.multiple_results else [ans]
        for var, val in zip(eqn.outvars, outs):
            if not isinstance(var, _DropVar):
                env[var] = val


def _read(v, env):
    return jnp.asarray(v.val) if isinstance(v, _Literal) else env[v]


def _find_wgrad_routes(jaxpr, w_eqns, dp_vars):
    """Terminal ``dW = a^T @ g`` patterns eligible for fused accumulation.

    Matches a dp output produced (within the W slice) by either
    ``dot_general(u, v)`` contracting dim 0 with dim 0 (dW = u^T v), or the
    same followed by a rank-2 ``transpose`` (dW = v^T u).  The matched
    equations can then be *replaced* by one `wgrad_accum` call.
    """
    producer = {}
    use_count: Dict[Any, int] = {}
    w_set = set(w_eqns)
    for i, eqn in enumerate(jaxpr.eqns):
        for ov in eqn.outvars:
            if not isinstance(ov, _DropVar):
                producer[ov] = i
        for v in eqn.invars:
            if isinstance(v, _Var):
                use_count[v] = use_count.get(v, 0) + 1
    for v in jaxpr.outvars:
        if isinstance(v, _Var):
            use_count[v] = use_count.get(v, 0) + 1

    def _is_wgrad_dot(eqn):
        # dW = a^T @ g with the token dims flattened: contract every leading
        # dim of both rank-k operands (k >= 2), no batch dims.
        if eqn.primitive.name != "dot_general":
            return False
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        if lb or rb:
            return False
        if not all(
            isinstance(v, _Var) and len(v.aval.shape) >= 2 for v in eqn.invars
        ):
            return False
        k = len(eqn.invars[0].aval.shape)
        lead = tuple(range(k - 1))
        return (
            len(eqn.invars[1].aval.shape) == k
            and tuple(lc) == lead
            and tuple(rc) == lead
        )

    routes = []
    for dp in dp_vars:
        route = None
        i = producer.get(dp)
        if i is not None and i in w_set and use_count.get(dp, 0) == 1:
            eqn = jaxpr.eqns[i]
            if _is_wgrad_dot(eqn):
                u, v = eqn.invars
                route = ("fuse", u, v, frozenset([i]))
            elif (
                eqn.primitive.name == "transpose"
                and tuple(eqn.params["permutation"]) == (1, 0)
                and isinstance(eqn.invars[0], _Var)
                and use_count.get(eqn.invars[0], 0) == 1
            ):
                j = producer.get(eqn.invars[0])
                if j is not None and j in w_set and _is_wgrad_dot(jaxpr.eqns[j]):
                    u, v = jaxpr.eqns[j].invars
                    route = ("fuse", v, u, frozenset([i, j]))
        routes.append(route)
    return routes


# --------------------------------------------------------------------- #
# flat backward IR: wrapper inlining + synthetic (split) scan equations
# --------------------------------------------------------------------- #
#: primitives cheap enough to re-evaluate on the W side (elementwise, shape,
#: reductions -- all O(bytes touched)); anything outside this set is pinned
#: to the slice the base partition put it in.  GEMMs / scans / collectives
#: are deliberately absent: B and W each keep exactly one backward matmul
#: per forward matmul (paper Table 1) and collectives fire once per slice.
_REPLAYABLE = frozenset(
    {
        "abs", "acos", "acosh", "add", "add_any", "and", "asin", "asinh",
        "atan", "atan2", "atanh", "bitcast_convert_type", "broadcast_in_dim",
        "cbrt", "ceil", "clamp", "concatenate", "convert_element_type",
        "copy", "cos", "cosh", "cumlogsumexp", "cummax", "cummin", "cumprod",
        "cumsum", "div", "dynamic_slice", "dynamic_update_slice", "eq",
        "erf", "erf_inv", "erfc", "exp", "exp2", "expm1", "floor", "ge",
        "gt", "imag", "integer_pow", "iota", "is_finite", "le", "log",
        "log1p", "logistic", "lt", "max", "min", "mul", "ne", "neg",
        "nextafter", "not", "or", "pad", "pow", "real", "reduce_and",
        "reduce_max", "reduce_min", "reduce_or", "reduce_prod", "reduce_sum",
        "rem", "reshape", "rev", "round", "rsqrt", "select_n", "shift_left",
        "shift_right_arithmetic", "shift_right_logical", "sign", "sin",
        "sinh", "slice", "split", "sqrt", "squeeze", "sub", "tan", "tanh",
        "transpose", "xor",
    }
)

#: wrapper primitives whose body jaxpr is inlined before partitioning, so
#: the cut can recurse into remat'd / custom-vjp'd / jitted sub-programs
_WRAPPER_PRIMS = (
    "pjit", "remat2", "checkpoint", "custom_jvp_call",
    "custom_jvp_call_jaxpr", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "closed_call", "core_call",
)

_BIG = 1 << 62  # "infinite" capacity / not storable


@dataclasses.dataclass
class _FlatIR:
    """A flattened jaxpr stand-in (post wrapper inlining, scan rewriting).

    Quacks like a Jaxpr for everything the partitioner and the slice
    evaluators touch: ``constvars`` / ``invars`` / ``outvars`` / ``eqns``.
    """

    constvars: List[Any]
    invars: List[Any]
    outvars: List[Any]
    eqns: List[Any]


class _SynthPrim:
    multiple_results = True

    def __init__(self, name: str):
        self.name = name


class _SynthScanEqn:
    """One half of a split scan: evaluated via ``run`` instead of bind.

    ``run(invals)`` returns one value per outvar.  Exposes ``invars`` /
    ``outvars`` / ``primitive`` / ``params`` so the partition walks treat it
    like any other (non-replayable) equation.
    """

    def __init__(self, name, invars, outvars, run):
        self.primitive = _SynthPrim(name)
        self.invars = list(invars)
        self.outvars = list(outvars)
        self.params: Dict[str, Any] = {}
        self.run = run
        # set to the split scan's own by _split_scans
        self.source_info = _src_info.new_source_info()


def _eqn_replace(eqn, invars=None, outvars=None):
    kw = {}
    if invars is not None:
        kw["invars"] = invars
    if outvars is not None:
        kw["outvars"] = outvars
    return eqn.replace(**kw)


def _clone_body(jaxpr):
    """Fresh-var copy of a body jaxpr (vars only; primitives are shared)."""
    m: Dict[Any, Any] = {}

    def mv(v):
        if isinstance(v, _Literal) or isinstance(v, _DropVar):
            return v
        if not isinstance(v, _Var):
            return v
        if v not in m:
            m[v] = _Var(v.aval)
        return m[v]

    eqns = [
        _eqn_replace(
            e,
            invars=[mv(v) for v in e.invars],
            outvars=[mv(v) for v in e.outvars],
        )
        for e in jaxpr.eqns
    ]
    return (
        [mv(v) for v in jaxpr.constvars],
        [mv(v) for v in jaxpr.invars],
        [mv(v) for v in jaxpr.outvars],
        eqns,
    )


def _wrapper_body(eqn):
    """(body_jaxpr, body_consts) for an inlinable wrapper eqn, else None."""
    name = getattr(eqn.primitive, "name", "")
    if name not in _WRAPPER_PRIMS:
        return None
    params = eqn.params
    cand = (
        params.get("jaxpr")
        or params.get("call_jaxpr")
        or params.get("fun_jaxpr")
    )
    if cand is None:
        return None
    if hasattr(cand, "jaxpr"):  # ClosedJaxpr
        return cand.jaxpr, list(cand.consts)
    return cand, []


def _inline_wrappers(jaxpr, consts) -> Tuple[_FlatIR, List[Any]]:
    """Flatten pjit / remat / custom-vjp wrappers into one equation list."""
    constvars = list(jaxpr.constvars)
    new_consts = list(consts)
    rename: Dict[Any, Any] = {}

    def res(v):
        while isinstance(v, _Var) and not isinstance(v, _DropVar) and v in rename:
            v = rename[v]
        return v

    out_eqns: List[Any] = []

    def emit(eqn, depth):
        eqn = _eqn_replace(eqn, invars=[res(v) for v in eqn.invars])
        body = _wrapper_body(eqn) if depth < 16 else None
        if body is None:
            out_eqns.append(eqn)
            return
        bjaxpr, bconsts = body
        cvs, ivs, ovs, beqns = _clone_body(bjaxpr)
        constvars.extend(cvs)
        new_consts.extend(bconsts)
        for bi, outer in zip(ivs, eqn.invars):
            rename[bi] = outer
        for be in beqns:
            emit(be, depth + 1)
        for bo, oo in zip(ovs, eqn.outvars):
            if isinstance(oo, _DropVar):
                continue
            rename[oo] = res(bo)

    for e in jaxpr.eqns:
        emit(e, 0)
    outvars = [res(v) for v in jaxpr.outvars]
    return _FlatIR(constvars, list(jaxpr.invars), outvars, out_eqns), new_consts


def _aval_bytes(v) -> int:
    aval = v.aval
    n = 1
    for d in aval.shape:
        n *= int(d)
    return max(1, n * jnp.dtype(aval.dtype).itemsize)


def _needed_vars(eqns, targets):
    """Vars transitively needed to compute ``targets`` (backward slice)."""
    need = set(v for v in targets if isinstance(v, _Var))
    for eqn in reversed(eqns):
        if any(ov in need for ov in eqn.outvars):
            need.update(v for v in eqn.invars if isinstance(v, _Var))
    return need


def _slice_eqns(eqns, targets, stop):
    """Equation ids needed for ``targets``, not walking past ``stop`` vars."""
    need = set(v for v in targets if isinstance(v, _Var) and v not in stop)
    ids: List[int] = []
    for i in range(len(eqns) - 1, -1, -1):
        eqn = eqns[i]
        if any(ov in need for ov in eqn.outvars):
            ids.append(i)
            need.update(
                v
                for v in eqn.invars
                if isinstance(v, _Var) and v not in stop
            )
    ids.reverse()
    return ids, need


class _Dinic:
    def __init__(self, n):
        self.n = n
        self.head: List[List[int]] = [[] for _ in range(n)]
        self.to: List[int] = []
        self.cap: List[int] = []

    def edge(self, u, v, c):
        self.head[u].append(len(self.to))
        self.to.append(v)
        self.cap.append(c)
        self.head[v].append(len(self.to))
        self.to.append(u)
        self.cap.append(0)

    def _bfs(self, s, t):
        self.level = [-1] * self.n
        self.level[s] = 0
        q = [s]
        for u in q:
            for ei in self.head[u]:
                v = self.to[ei]
                if self.cap[ei] > 0 and self.level[v] < 0:
                    self.level[v] = self.level[u] + 1
                    q.append(v)
        return self.level[t] >= 0

    def _augment(self, s, t):
        """One blocking-path augmentation (iterative DFS)."""
        path: List[int] = []  # edge ids along the current path
        u = s
        while True:
            if u == t:
                f = min(self.cap[ei] for ei in path)
                for ei in path:
                    self.cap[ei] -= f
                    self.cap[ei ^ 1] += f
                return f
            advanced = False
            while self.it[u] < len(self.head[u]):
                ei = self.head[u][self.it[u]]
                v = self.to[ei]
                if self.cap[ei] > 0 and self.level[v] == self.level[u] + 1:
                    path.append(ei)
                    u = v
                    advanced = True
                    break
                self.it[u] += 1
            if advanced:
                continue
            self.level[u] = -1  # dead end
            if not path:
                return 0
            u = self.to[path.pop() ^ 1]
            self.it[u] += 1

    def max_flow(self, s, t):
        flow = 0
        while self._bfs(s, t):
            self.it = [0] * self.n
            while True:
                f = self._augment(s, t)
                if f == 0:
                    break
                flow += f
        return flow

    def s_side(self, s):
        seen = [False] * self.n
        seen[s] = True
        q = [s]
        for u in q:
            for ei in self.head[u]:
                v = self.to[ei]
                if self.cap[ei] > 0 and not seen[v]:
                    seen[v] = True
                    q.append(v)
        return seen


def _byte_min_cut(
    eqns, targets, is_free, invar_cap, b_mand_set, cap_of=None,
    w_only=frozenset(),
):
    """Byte-minimal storable-var cut separating B-time values from W needs.

    Every var the W slice ultimately depends on must be either *free*
    (re-injectable params/side, plan consts), *stored* (part of the M_W
    context, costing its bytes), or *derivable* from stored/free vars by
    replaying cheap (``_REPLAYABLE``) equations.  Non-replayable equations
    that the base partition put in B (``b_mand_set``) produce storable
    origins; non-replayable equations left to W are not storable (they run
    only at W time) and propagate the need to their inputs.  ``w_only``
    vars (e.g. the W scan's own accumulator carries at body level) exist
    only at W time: they and anything computed from them can be consumed
    by W for free but never stored, so the cut lands on their B-side
    co-inputs instead.

    Returns the cut as a set of vars, or ``None`` when no finite cut exists
    / the cone is degenerate (caller falls back to the frontier cut).
    """
    producer: Dict[Any, int] = {}
    for i, e in enumerate(eqns):
        for ov in e.outvars:
            if isinstance(ov, _Var) and not isinstance(ov, _DropVar):
                producer[ov] = i

    cap_of = cap_of or _aval_bytes
    tgt = [v for v in targets if isinstance(v, _Var) and not is_free(v)]
    if not tgt:
        return set()

    nodes: List[Any] = []
    idx: Dict[Any, int] = {}
    caps: Dict[Any, int] = {}
    preds: Dict[Any, List[Any]] = {}
    origin: set = set()
    stack = list(dict.fromkeys(tgt))
    seen = set(stack)
    while stack:
        v = stack.pop()
        idx[v] = len(nodes)
        nodes.append(v)
        if v in w_only:
            caps[v] = _BIG  # exists only at W time; never storable
            continue
        i = producer.get(v)
        if i is None:
            c = invar_cap(v)
            if c is None:
                return None  # un-derivable, un-storable leaf
            caps[v] = c
            origin.add(v)
            continue
        e = eqns[i]
        replayable = (
            not isinstance(e, _SynthScanEqn)
            and e.primitive.name in _REPLAYABLE
        )
        if replayable:
            caps[v] = cap_of(v)
            ins = [
                u
                for u in e.invars
                if isinstance(u, _Var) and not is_free(u)
            ]
            preds[v] = ins
        elif i in b_mand_set:
            caps[v] = cap_of(v)  # materialized by B anyway: storable origin
            origin.add(v)
            continue
        else:
            caps[v] = _BIG  # runs only at W time: not storable
            ins = [
                u
                for u in e.invars
                if isinstance(u, _Var) and not is_free(u)
            ]
            preds[v] = ins
        for u in preds.get(v, ()):
            if u not in seen:
                seen.add(u)
                stack.append(u)

    if len(nodes) > 20000:
        return None  # pathological cone; keep the frontier cut

    # storable == B-computable: an origin, or a replayable chain over
    # B-computable inputs.  Anything downstream of a W-pinned equation only
    # exists at W time and must keep infinite capacity.
    order = sorted(
        nodes, key=lambda v: -1 if producer.get(v) is None else producer[v]
    )
    computable = set()
    for v in order:
        if v in origin:
            computable.add(v)
        elif v in preds and caps[v] < _BIG:
            # replayable: B-computable iff every stored/derived input is
            # (empty preds == derivable from free inputs alone)
            if all(u in computable for u in preds[v]):
                computable.add(v)
            else:
                caps[v] = _BIG

    # vertex-split flow network: v_in = 2k, v_out = 2k+1
    N = 2 * len(nodes) + 2
    S, T = N - 2, N - 1
    g = _Dinic(N)
    for v in nodes:
        k = idx[v]
        g.edge(2 * k, 2 * k + 1, caps[v])
    for v in nodes:
        for u in preds.get(v, ()):
            g.edge(2 * idx[u] + 1, 2 * idx[v], _BIG)
    for v in origin:
        g.edge(S, 2 * idx[v], _BIG)
    for v in dict.fromkeys(tgt):
        g.edge(2 * idx[v] + 1, T, _BIG)
    flow = g.max_flow(S, T)
    if flow >= _BIG:
        return None
    side = g.s_side(S)
    cut = set(
        v for v in nodes if side[2 * idx[v]] and not side[2 * idx[v] + 1]
    )
    return cut


# --------------------------------------------------------------------- #
# recursive scan split (the recurrent B/W split)
# --------------------------------------------------------------------- #
def _scan_arity(eqn):
    nc = eqn.params["num_consts"]
    nk = eqn.params["num_carry"]
    return nc, nk


def _split_one_scan(eqn, need_dx, need_dp):
    """Split a backward ``scan`` into a dx-only B scan + a dp replay W scan.

    Returns ``(b_eqn | None, w_eqn | None)`` or ``None`` when the equation
    should be left untouched.  The B scan keeps the recurrence (all carries
    the dx slice depends on) and, besides the dx-needed stacked outputs,
    emits the *per-step compact W context* as extra stacked outputs -- the
    byte-minimal body cut.  The W scan replays the dp slice of the body over
    that stacked context; dp-only accumulator carries (e.g. a dW summed
    across steps) move into it wholesale, so their GEMMs run at W time.
    """
    closed = eqn.params["jaxpr"]
    nc, nk = _scan_arity(eqn)
    length = eqn.params["length"]
    reverse = eqn.params["reverse"]
    body, body_consts = _inline_wrappers(closed.jaxpr, list(closed.consts))
    if any(isinstance(c, jax.core.Tracer) for c in body_consts):
        return None

    const_ivs = body.invars[:nc]
    carry_ivs = body.invars[nc : nc + nk]
    xs_ivs = body.invars[nc + nk :]
    carry_ovs = body.outvars[:nk]
    y_ovs = body.outvars[nk:]
    outer_consts = eqn.invars[:nc]
    outer_inits = eqn.invars[nc : nc + nk]
    outer_xs = eqn.invars[nc + nk :]
    outer_carry_outs = eqn.outvars[:nk]
    outer_ys = eqn.outvars[nk:]

    def _o_needed(ov, need):
        return isinstance(ov, _Var) and not isinstance(ov, _DropVar) and ov in need

    dx_ys = [j for j, ov in enumerate(outer_ys) if _o_needed(ov, need_dx)]
    dp_ys = [
        j
        for j, ov in enumerate(outer_ys)
        if _o_needed(ov, need_dp) and not _o_needed(ov, need_dx)
    ]
    eqn_dx = any(_o_needed(ov, need_dx) for ov in eqn.outvars)
    eqn_dp = any(_o_needed(ov, need_dp) for ov in eqn.outvars)

    # ---- case B: scan needed only for dp -> run whole in W, prune inputs #
    if not eqn_dx:
        if not eqn_dp:
            return None  # dead scan
        keep_c = set(
            i
            for i in range(nk)
            if _o_needed(outer_carry_outs[i], need_dp)
        )
        while True:
            targets = [carry_ovs[i] for i in keep_c] + [y_ovs[j] for j in dp_ys]
            wneed = _needed_vars(body.eqns, targets)
            grow = set(
                i
                for i in range(nk)
                if i not in keep_c
                and (carry_ivs[i] in wneed or carry_ovs[i] in wneed)
            )
            if not grow:
                break
            keep_c |= grow
        keep_c = sorted(keep_c)
        targets = [carry_ovs[i] for i in keep_c] + [y_ovs[j] for j in dp_ys]
        w_ids, wneed = _slice_eqns(body.eqns, targets, set())
        used_const = [k for k, v in enumerate(const_ivs) if v in wneed]
        used_xs = [k for k, v in enumerate(xs_ivs) if v in wneed]
        if (
            len(used_const) == nc
            and len(used_xs) == len(xs_ivs)
            and len(keep_c) == nk
            and len(dp_ys) == len(outer_ys)
        ):
            return None  # nothing prunable: keep the original equation
        w_eqn = _make_scan_half(
            f"{eqn.primitive.name}_w",
            body, body_consts, w_ids,
            const_pos=used_const, const_atoms=[outer_consts[k] for k in used_const],
            carry_pos=keep_c, carry_inits=[outer_inits[i] for i in keep_c],
            xs_pos=used_xs, xs_atoms=[outer_xs[k] for k in used_xs],
            ctx_vars=[], ctx_atoms=[],
            const_ivs=const_ivs, carry_ivs=carry_ivs, xs_ivs=xs_ivs,
            carry_ovs=carry_ovs, y_ovs=y_ovs,
            out_carries=keep_c,
            out_carry_atoms=[outer_carry_outs[i] for i in keep_c],
            out_ys=dp_ys, out_y_atoms=[outer_ys[j] for j in dp_ys],
            length=length, reverse=reverse,
        )
        return None, w_eqn

    # ---- case A: dual-use scan -> split the body ----------------------- #
    if not dp_ys and all(
        not _o_needed(outer_carry_outs[i], need_dp)
        or _o_needed(outer_carry_outs[i], need_dx)
        for i in range(nk)
    ):
        return None  # every dp-needed output is dx-needed anyway

    # carries whose final value is dp-only (or unused) may move to the W
    # scan -- unless the B slice of the body consumes their chain
    cand = set(
        i
        for i in range(nk)
        if not _o_needed(outer_carry_outs[i], need_dx)
    )
    while True:
        b_targets = [carry_ovs[i] for i in range(nk) if i not in cand] + [
            y_ovs[j] for j in dx_ys
        ]
        bneed = _needed_vars(body.eqns, b_targets)
        promote = set(
            i
            for i in cand
            if carry_ivs[i] in bneed or carry_ovs[i] in bneed
        )
        if not promote:
            break
        cand -= promote
    # W carries: candidates the dp side actually needs -- final value
    # dp-needed, or chain feeding the dp-only ys / other W-carry chains
    w_carries = set(
        i for i in cand if _o_needed(outer_carry_outs[i], need_dp)
    )
    while True:
        wneed0 = _needed_vars(
            body.eqns,
            [y_ovs[j] for j in dp_ys] + [carry_ovs[i] for i in w_carries],
        )
        grow = set(
            i
            for i in cand
            if i not in w_carries
            and (carry_ivs[i] in wneed0 or carry_ovs[i] in wneed0)
        )
        if not grow:
            break
        w_carries |= grow
    w_carries = sorted(w_carries)
    b_carries = [i for i in range(nk) if i not in cand]
    b_targets = [carry_ovs[i] for i in b_carries] + [y_ovs[j] for j in dx_ys]
    b_ids_base, _ = _slice_eqns(body.eqns, b_targets, set())
    b_mand_body = set(b_ids_base)

    w_targets = [carry_ovs[i] for i in w_carries] + [y_ovs[j] for j in dp_ys]
    if not w_targets:
        return None

    const_set = set(const_ivs)
    wcarry_in = set(carry_ivs[i] for i in w_carries)
    body_const_set = set(body.constvars)

    # note: const positions whose outer atom is a Literal are NOT free --
    # they join the cut like any const, so both half-scans receive them as
    # invars (the outer evaluator resolves Literal invars natively).
    # W-carry-ins are *not* free either: they exist only at W time, so the
    # cut must never select a value computed from one (the B half could
    # not materialize it) -- they ride ``w_only`` instead.
    def body_free(v):
        return v in body_const_set

    def w_avail(v):
        return v in body_const_set or v in wcarry_in

    def body_invar_cap(v):
        if v in const_set:
            return _aval_bytes(v)  # one copy, shared across steps
        if v in body_const_set:
            return None  # free; never reaches here
        # carry-in / xs: storing means a stacked per-step context
        return _aval_bytes(v) * int(length)

    cut = _byte_min_cut(
        body.eqns,
        w_targets,
        body_free,
        body_invar_cap,
        b_mand_body,
        cap_of=lambda v: _aval_bytes(v) * int(length),
        w_only=wcarry_in,
    )
    if cut is None:
        return None

    w_ids, wneed = _slice_eqns(body.eqns, w_targets, set(cut))
    # leaf validation: everything W consumes must be cut, free, or carried
    leaf_need = set()
    for i in w_ids:
        for v in body.eqns[i].invars:
            if isinstance(v, _Var) and v not in cut and not w_avail(v):
                leaf_need.add(v)
    prod_ok = set()
    for i in w_ids:
        for ov in body.eqns[i].outvars:
            prod_ok.add(ov)
    for v in w_targets:
        if isinstance(v, _Var) and v not in prod_ok and v not in cut and not w_avail(v):
            return None
    for v in leaf_need:
        if v not in prod_ok:
            return None

    const_cut = [k for k, v in enumerate(const_ivs) if v in cut]
    xs_cut = [k for k, v in enumerate(xs_ivs) if v in cut]
    ctx_vars = [
        v
        for v in sorted(
            (v for v in cut if v not in const_set and v not in set(xs_ivs)),
            key=lambda v: _body_order_key(body, v),
        )
    ]

    # B slice must additionally materialize the per-step context
    b_ids, bneed = _slice_eqns(
        body.eqns, b_targets + list(ctx_vars), set()
    )
    if bneed & wcarry_in:
        return None  # B half would need a W-only carry: cannot split
    b_const = [k for k, v in enumerate(const_ivs) if v in bneed]
    b_xs = [k for k, v in enumerate(xs_ivs) if v in bneed]

    ctx_atoms = [
        _Var(
            jax.core.ShapedArray(
                (int(length),) + tuple(v.aval.shape), v.aval.dtype
            )
        )
        for v in ctx_vars
    ]
    b_eqn = _make_scan_half(
        f"{eqn.primitive.name}_b",
        body, body_consts, b_ids,
        const_pos=b_const, const_atoms=[outer_consts[k] for k in b_const],
        carry_pos=b_carries, carry_inits=[outer_inits[i] for i in b_carries],
        xs_pos=b_xs, xs_atoms=[outer_xs[k] for k in b_xs],
        ctx_vars=[], ctx_atoms=[],
        const_ivs=const_ivs, carry_ivs=carry_ivs, xs_ivs=xs_ivs,
        carry_ovs=carry_ovs, y_ovs=y_ovs,
        out_carries=b_carries,
        out_carry_atoms=[outer_carry_outs[i] for i in b_carries],
        out_ys=dx_ys, out_y_atoms=[outer_ys[j] for j in dx_ys],
        length=length, reverse=reverse,
        emit_ctx=ctx_vars, emit_ctx_atoms=ctx_atoms,
    )
    w_eqn = _make_scan_half(
        f"{eqn.primitive.name}_w",
        body, body_consts, w_ids,
        const_pos=const_cut, const_atoms=[outer_consts[k] for k in const_cut],
        carry_pos=list(w_carries), carry_inits=[outer_inits[i] for i in w_carries],
        xs_pos=xs_cut, xs_atoms=[outer_xs[k] for k in xs_cut],
        ctx_vars=ctx_vars, ctx_atoms=ctx_atoms,
        const_ivs=const_ivs, carry_ivs=carry_ivs, xs_ivs=xs_ivs,
        carry_ovs=carry_ovs, y_ovs=y_ovs,
        out_carries=list(w_carries),
        out_carry_atoms=[outer_carry_outs[i] for i in w_carries],
        out_ys=dp_ys, out_y_atoms=[outer_ys[j] for j in dp_ys],
        length=length, reverse=reverse,
    )
    return b_eqn, w_eqn


def _body_order_key(body, v):
    for i, e in enumerate(body.eqns):
        if v in e.outvars:
            return (1, i)
    try:
        return (0, body.invars.index(v))
    except ValueError:
        return (2, 0)


def _make_scan_half(
    name, body, body_consts, eqn_ids, *,
    const_pos, const_atoms, carry_pos, carry_inits, xs_pos, xs_atoms,
    ctx_vars, ctx_atoms, const_ivs, carry_ivs, xs_ivs, carry_ovs, y_ovs,
    out_carries, out_carry_atoms, out_ys, out_y_atoms, length, reverse,
    emit_ctx=(), emit_ctx_atoms=(),
):
    """Build one synthetic half-scan equation over a body slice.

    Inputs: selected outer consts, carry inits, stacked xs, and (for the W
    half) the stacked per-step context the B half emitted.  Outputs: the
    selected final carries and stacked ys, plus (for the B half) the stacked
    context.  Evaluation re-traces the body slice under ``jax.lax.scan``
    with the original ``reverse``/``length``, so per-index alignment and
    accumulation order match the unsplit scan exactly.
    """
    n_const = len(const_pos)
    n_carry = len(carry_pos)
    n_xs = len(xs_pos)
    const_vars = [const_ivs[k] for k in const_pos]
    carry_in_vars = [carry_ivs[i] for i in carry_pos]
    carry_out_vars = [carry_ovs[i] for i in carry_pos]
    xs_vars = [xs_ivs[k] for k in xs_pos]
    y_out_vars = [y_ovs[j] for j in out_ys]
    emit_ctx = list(emit_ctx)

    def run(invals):
        consts_v = invals[:n_const]
        inits = tuple(invals[n_const : n_const + n_carry])
        xs_v = tuple(invals[n_const + n_carry : n_const + n_carry + n_xs])
        ctx_v = tuple(invals[n_const + n_carry + n_xs :])

        def step(carry, sl):
            xsl = sl[:n_xs]
            ctxl = sl[n_xs:]
            env = dict(zip(body.constvars, body_consts))
            env.update(zip(const_vars, consts_v))
            env.update(zip(carry_in_vars, carry))
            env.update(zip(xs_vars, xsl))
            env.update(zip(ctx_vars, ctxl))
            _eval_eqns(body, eqn_ids, env)
            new_carry = tuple(_read(v, env) for v in carry_out_vars)
            ys = tuple(_read(v, env) for v in y_out_vars) + tuple(
                env[v] for v in emit_ctx
            )
            return new_carry, ys

        fin, ys = jax.lax.scan(
            step,
            inits,
            tuple(xs_v) + tuple(ctx_v),
            length=int(length),
            reverse=reverse,
        )
        return list(fin) + list(ys)

    invars = list(const_atoms) + list(carry_inits) + list(xs_atoms) + list(
        ctx_atoms
    )
    outvars = list(out_carry_atoms) + list(out_y_atoms) + list(emit_ctx_atoms)
    eqn = _SynthScanEqn(name, invars, outvars, run)
    # introspection (tests assert e.g. that the per-step wgrad GEMMs moved
    # into the W half): the body slice this half evaluates per step
    eqn.body = body
    eqn.body_eqn_ids = list(eqn_ids)
    eqn.n_ctx = len(ctx_atoms) + len(emit_ctx_atoms)
    return eqn


def _split_scans(ir: _FlatIR, need_dx, need_dp) -> bool:
    """Rewrite splittable ``scan`` equations in place; True when changed."""
    changed = False
    new_eqns: List[Any] = []
    for eqn in ir.eqns:
        if (
            isinstance(eqn, _SynthScanEqn)
            or getattr(eqn.primitive, "name", "") != "scan"
        ):
            new_eqns.append(eqn)
            continue
        try:
            halves = _split_one_scan(eqn, need_dx, need_dp)
        except (KeyError, ValueError, TypeError):
            halves = None
        if halves is None:
            new_eqns.append(eqn)
            continue
        for half in halves:
            if half is not None:
                half.source_info = eqn.source_info
                new_eqns.append(half)
        changed = True
    if changed:
        ir.eqns = new_eqns
    return changed


# --------------------------------------------------------------------- #
# the compact partition: scan split + byte-minimal context
# --------------------------------------------------------------------- #
def _compact_partition(ir: _FlatIR, n_p: int, n_s: int, dp_vars, dx_vars):
    """(b_eqns, w_eqns, cut_vars, reinject) or None -> frontier fallback."""
    need_dx = _needed_vars(ir.eqns, dx_vars)
    need_dp = _needed_vars(ir.eqns, dp_vars)
    if _split_scans(ir, need_dx, need_dp):
        need_dx = _needed_vars(ir.eqns, dx_vars)

    invar_idx = {v: i for i, v in enumerate(ir.invars)}
    constset = set(ir.constvars)

    def is_free(v):
        if v in constset:
            return True
        i = invar_idx.get(v)
        return i is not None and i < n_p + n_s

    def invar_cap(v):
        if invar_idx.get(v) is None:
            return None
        return _aval_bytes(v)

    b_mand = set(
        i
        for i, e in enumerate(ir.eqns)
        if any(
            isinstance(ov, _Var)
            and not isinstance(ov, _DropVar)
            and ov in need_dx
            for ov in e.outvars
        )
    )
    cut = _byte_min_cut(ir.eqns, dp_vars, is_free, invar_cap, b_mand)
    if cut is None:
        return None

    producer = {}
    for i, e in enumerate(ir.eqns):
        for ov in e.outvars:
            if isinstance(ov, _Var) and not isinstance(ov, _DropVar):
                producer[ov] = i

    def order_key(v):
        i = producer.get(v)
        if i is None:
            return (0, invar_idx.get(v, 0))
        return (1, i)

    cut_vars = sorted(cut, key=order_key)

    w_eqns, w_need = _slice_eqns(ir.eqns, list(dp_vars), cut)
    # consistency: W may evaluate replayable equations, its own pinned
    # equations, and synthetic W scans -- never a non-replayable equation
    # the B slice owns
    for i in w_eqns:
        e = ir.eqns[i]
        replayable = (
            not isinstance(e, _SynthScanEqn)
            and e.primitive.name in _REPLAYABLE
        )
        if not replayable and i in b_mand:
            return None
    for v in w_need:
        if producer.get(v) is None and not is_free(v) and v not in cut:
            return None

    b_eqns, _ = _slice_eqns(ir.eqns, list(dx_vars) + cut_vars, set())

    reinject: Dict[Any, int] = {}
    for i in w_eqns:
        for v in ir.eqns[i].invars:
            if isinstance(v, _Var):
                j = invar_idx.get(v)
                if j is not None and j < n_p + n_s:
                    reinject[v] = j
    for v in dp_vars:
        if isinstance(v, _Var):
            j = invar_idx.get(v)
            if j is not None and j < n_p + n_s:
                reinject[v] = j
    return b_eqns, w_eqns, cut_vars, reinject


class _AutoFBW(FBWModule):
    def __init__(
        self,
        f: Callable[[PyTree, PyTree, PyTree], PyTree],
        init_fn: Optional[Callable[[jax.Array], PyTree]] = None,
        name: str = "auto",
        compact: Optional[bool] = None,
    ):
        self.f = f
        self._init_fn = init_fn
        self.name = name
        self.compact = _COMPACT_DEFAULT if compact is None else bool(compact)
        self._treedef = None
        self._spec: Optional[List[Tuple[int, int]]] = None
        self._split: Optional[_SplitPlan] = None

    def init(self, key):
        if self._init_fn is None:
            raise NotImplementedError(f"{self.name}: no init_fn provided")
        return self._init_fn(key)

    # -- forward ---------------------------------------------------------- #
    def fwd(self, params, x, side):
        y, pullback = jax.vjp(lambda p, xx: self.f(p, xx, side), params, x)
        leaves, treedef = jax.tree_util.tree_flatten(pullback)
        self._treedef = treedef
        by_id = {}
        for i, leaf in enumerate(jax.tree_util.tree_leaves(params)):
            by_id.setdefault(id(leaf), (_PARAM, i))
        for i, leaf in enumerate(jax.tree_util.tree_leaves(side)):
            by_id.setdefault(id(leaf), (_SIDE, i))
        spec: List[Tuple[int, int]] = []
        stored = []
        for leaf in leaves:
            hit = by_id.get(id(leaf))
            if hit is not None:
                spec.append(hit)
            else:
                spec.append((_STORE, len(stored)))
                stored.append(leaf)
        self._spec = spec
        return y, tuple(stored)

    def _rebuild(self, params, stored, side):
        if self._treedef is None or self._spec is None:
            raise RuntimeError(
                f"{self.name}: fwd must be traced before bwd (call "
                "ensure_traced or run fwd under jax.eval_shape first)"
            )
        p_leaves = jax.tree_util.tree_leaves(params)
        s_leaves = jax.tree_util.tree_leaves(side)
        leaves = []
        for kind, i in self._spec:
            if kind == _STORE:
                leaves.append(stored[i])
            elif kind == _PARAM:
                leaves.append(p_leaves[i])
            else:
                leaves.append(s_leaves[i])
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    # -- backward-jaxpr partition ------------------------------------------ #
    def _ensure_split(self, params, res, dy, side) -> _SplitPlan:
        key = _avals_key(params, res, dy, side)
        if self._split is not None and self._split.key == key:
            return self._split

        p_leaves = jax.tree_util.tree_leaves(params)
        s_leaves = jax.tree_util.tree_leaves(side)
        dy_leaves, dy_tree = jax.tree_util.tree_flatten(dy)
        n_p, n_s = len(p_leaves), len(s_leaves)

        def joint(pl, sl, st, dyl):
            p2 = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(params), pl
            )
            s2 = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(side), sl
            )
            pb = self._rebuild(p2, st, s2)
            dp, dx = pb(jax.tree_util.tree_unflatten(dy_tree, dyl))
            return dp, dx

        closed, out_shape = jax.make_jaxpr(joint, return_shape=True)(
            p_leaves, s_leaves, list(res), dy_leaves
        )
        if any(isinstance(c, jax.core.Tracer) for c in closed.consts):
            raise RuntimeError(
                f"{self.name}: backward jaxpr captured tracer constants; "
                "route all data through params/x/side"
            )
        jaxpr = closed.jaxpr
        consts = list(closed.consts)
        dp_shape, dx_shape = out_shape
        dp_tree = jax.tree_util.tree_structure(dp_shape)
        dx_tree = jax.tree_util.tree_structure(dx_shape)
        n_dp = dp_tree.num_leaves

        part = None
        if self.compact:
            ir, consts_i = _inline_wrappers(jaxpr, consts)
            if not any(isinstance(c, jax.core.Tracer) for c in consts_i):
                dp_vars = list(ir.outvars[:n_dp])
                dx_vars = list(ir.outvars[n_dp:])
                part = _compact_partition(ir, n_p, n_s, dp_vars, dx_vars)
                if part is not None:
                    b_eqns, w_eqns, cut_vars, reinject = part
                    jaxpr, consts = ir, consts_i

        if part is None:
            # frontier cut (the legacy partition; also the compat baseline)
            dp_vars = list(jaxpr.outvars[:n_dp])
            dx_vars = list(jaxpr.outvars[n_dp:])
            need_dx = _needed_vars(jaxpr.eqns, dx_vars)
            need_dp = _needed_vars(jaxpr.eqns, dp_vars)
            b_eqns = [
                i
                for i, e in enumerate(jaxpr.eqns)
                if any(ov in need_dx for ov in e.outvars)
            ]
            b_set = set(b_eqns)
            w_eqns = [
                i
                for i, e in enumerate(jaxpr.eqns)
                if i not in b_set and any(ov in need_dp for ov in e.outvars)
            ]
            w_prod = set(ov for i in w_eqns for ov in jaxpr.eqns[i].outvars)
            invar_idx = {v: i for i, v in enumerate(jaxpr.invars)}
            constvars = set(jaxpr.constvars)

            seen = set()
            cut_vars = []
            reinject = {}

            def classify(v):
                if not isinstance(v, _Var) or v in seen:
                    return
                seen.add(v)
                if v in w_prod or v in constvars:
                    return
                i = invar_idx.get(v)
                if i is not None and i < n_p + n_s:
                    reinject[v] = i  # param / side leaf: re-injected
                    return
                cut_vars.append(v)  # B-produced value or stored/dy leaf: M_W

            for i in w_eqns:
                for v in jaxpr.eqns[i].invars:
                    classify(v)
            for v in dp_vars:
                classify(v)

        self._split = _SplitPlan(
            jaxpr=jaxpr,
            consts=consts,
            b_eqns=b_eqns,
            w_eqns=w_eqns,
            cut_vars=cut_vars,
            reinject=reinject,
            dp_vars=dp_vars,
            dx_vars=dx_vars,
            dp_tree=dp_tree,
            dx_tree=dx_tree,
            n_p=n_p,
            n_s=n_s,
            wgrad_routes=_find_wgrad_routes(jaxpr, w_eqns, dp_vars),
            key=key,
        )
        return self._split

    # -- B: input gradient; emits the compact M_W context ------------------ #
    def bwd_x(self, params, res, dy, side):
        plan = self._ensure_split(params, res, dy, side)
        env = dict(zip(plan.jaxpr.constvars, plan.consts))
        flat = (
            jax.tree_util.tree_leaves(params)
            + jax.tree_util.tree_leaves(side)
            + list(res)
            + jax.tree_util.tree_leaves(dy)
        )
        env.update(zip(plan.jaxpr.invars, flat))
        _eval_eqns(plan.jaxpr, plan.b_eqns, env)
        dx = jax.tree_util.tree_unflatten(
            plan.dx_tree, [_read(v, env) for v in plan.dx_vars]
        )
        wctx = tuple(env[v] for v in plan.cut_vars)
        return dx, wctx

    # -- W: parameter gradient from the M_W context alone ------------------- #
    def bwd_w(self, params, wctx, side, acc=None):
        plan = self._split
        if plan is None:
            raise RuntimeError(
                f"{self.name}: bwd_x must be traced before bwd_w"
            )
        got = tuple(
            (tuple(w.shape), jnp.result_type(w).name) for w in wctx
        )
        want = tuple(
            (tuple(v.aval.shape), jnp.result_type(v.aval.dtype).name)
            for v in plan.cut_vars
        )
        if got != want:
            raise RuntimeError(
                f"{self.name}: wctx does not match the cached split (module "
                f"re-traced at different shapes between bwd_x and bwd_w?): "
                f"got {got[:4]}..., want {want[:4]}..."
            )
        env = dict(zip(plan.jaxpr.constvars, plan.consts))
        flat_ps = jax.tree_util.tree_leaves(params) + jax.tree_util.tree_leaves(
            side
        )
        for v, i in plan.reinject.items():
            env[v] = flat_ps[i]
        env.update(zip(plan.cut_vars, wctx))

        fused: Dict[int, Any] = {}
        skip = set()
        if acc is not None:
            acc_leaves = jax.tree_util.tree_leaves(acc)
            for k, route in enumerate(plan.wgrad_routes):
                if route is None:
                    continue
                a_leaf = acc_leaves[k]
                if jnp.result_type(a_leaf) != jnp.float32:
                    continue  # the fused kernel accumulates in fp32 only
                fused[k] = route
                skip |= set(route[3])
        _eval_eqns(plan.jaxpr, plan.w_eqns, env, skip=skip)

        if acc is None:
            grads = [_read(v, env) for v in plan.dp_vars]
            return jax.tree_util.tree_unflatten(plan.dp_tree, grads)

        from ..kernels.ops import wgrad_accum

        out = []
        for k, (v, a_leaf) in enumerate(zip(plan.dp_vars, acc_leaves)):
            route = fused.get(k)
            if route is not None:
                _, a_var, g_var, _ = route
                a = env[a_var]
                g = env[g_var]
                out.append(
                    wgrad_accum(
                        a.reshape(-1, a.shape[-1]),
                        g.reshape(-1, g.shape[-1]),
                        a_leaf,
                    )
                )
            else:
                g = _read(v, env)
                out.append(a_leaf + g.astype(a_leaf.dtype))
        return jax.tree_util.tree_unflatten(plan.dp_tree, out)

    def ensure_traced(self, params, x, side) -> None:
        """Populate the static residual spec without running any compute."""
        jax.eval_shape(lambda p, xx, sd: self.fwd(p, xx, sd), params, x, side)


def auto_fbw(
    f: Callable[[PyTree, PyTree, PyTree], PyTree],
    init_fn: Optional[Callable[[jax.Array], PyTree]] = None,
    name: str = "auto",
    compact: Optional[bool] = None,
) -> _AutoFBW:
    """Split any ``f(params, x, side) -> y`` into true F/B/W passes.

    ``compact`` (default: on, unless ``REPRO_SPLIT_COMPAT=1``) selects the
    byte-minimal W-context: wrapper inlining, the recursive scan split, and
    the min-cut with cheap W-side replay.  ``compact=False`` keeps the
    legacy frontier cut -- the pre-split baseline the measured-memory tests
    compare against.
    """
    return _AutoFBW(f, init_fn, name, compact=compact)


# --------------------------------------------------------------------- #
# sequential composition (a pipeline chunk = this stage's layer group)
# --------------------------------------------------------------------- #
class SequentialFBW(FBWModule):
    """Compose FBW modules; F runs left-to-right, B right-to-left.

    During B, each sub-module emits its own compact M_W context; the tuple
    of these per-block contexts is exactly the paper's "extra gradients
    (nabla_z L) kept for W" (Table 1) plus the wgrad matmul inputs.
    """

    def __init__(self, modules: Sequence[FBWModule], name: str = "seq"):
        self.modules = list(modules)
        self.name = name

    def init(self, key):
        keys = jax.random.split(key, len(self.modules))
        return tuple(mod.init(k) for mod, k in zip(self.modules, keys))

    def fwd(self, params, x, side):
        res_all = []
        for mod, p in zip(self.modules, params):
            x, res = mod.fwd(p, x, side)
            res_all.append(res)
        return x, tuple(res_all)

    def bwd_x(self, params, res, dy, side):
        wctx_all: List[PyTree] = [None] * len(self.modules)
        for i in reversed(range(len(self.modules))):
            dy, wctx = self.modules[i].bwd_x(params[i], res[i], dy, side)
            wctx_all[i] = wctx
        return dy, tuple(wctx_all)

    def bwd_w(self, params, wctx, side, acc=None):
        if acc is None:
            return tuple(
                mod.bwd_w(p, w, side)
                for mod, p, w in zip(self.modules, params, wctx)
            )
        return tuple(
            mod.bwd_w(p, w, side, acc=a)
            for mod, p, w, a in zip(self.modules, params, wctx, acc)
        )

    def ensure_traced(self, params, x, side) -> None:
        jax.eval_shape(lambda p, xx, sd: self.fwd(p, xx, sd), params, x, side)


def loss_seed(loss: jax.Array) -> jax.Array:
    """Cotangent that seeds B at the loss position."""
    return jnp.ones_like(loss)
