"""Shared test fixtures; provides a hypothesis fallback for offline runs.

The property tests use ``hypothesis`` when it is installed.  This container
has no network and no hypothesis wheel, so ``import hypothesis`` raises and
four test modules used to fail at collection.  When the real package is
missing we register a minimal seeded-random stand-in under the same module
names *before* the test modules import it: ``@given`` draws
``max_examples`` pseudo-random examples from the declared strategies and
runs the test once per draw (deterministic per test, seeded from the test's
qualified name).  The stand-in covers exactly the API surface the test
suite uses: ``given``, ``settings``, ``assume``, and the ``integers`` /
``sampled_from`` / ``booleans`` / ``floats`` strategies.
"""

import functools
import inspect
import random
import sys
import types
import zlib

try:  # pragma: no cover - prefer the real property-testing engine
    import hypothesis  # noqa: F401
except ImportError:
    _DEFAULT_MAX_EXAMPLES = 10

    class _Strategy:
        def __init__(self, draw):
            self.draw = draw

        def map(self, fn):
            return _Strategy(lambda rng: fn(self.draw(rng)))

        def filter(self, pred):
            def draw(rng):
                for _ in range(1000):
                    x = self.draw(rng)
                    if pred(x):
                        return x
                raise ValueError("filter predicate too strict")

            return _Strategy(draw)

    def integers(min_value, max_value):
        return _Strategy(lambda rng: rng.randint(min_value, max_value))

    def sampled_from(seq):
        items = list(seq)
        return _Strategy(lambda rng: rng.choice(items))

    def booleans():
        return _Strategy(lambda rng: rng.random() < 0.5)

    def floats(min_value=0.0, max_value=1.0, **_kw):
        return _Strategy(lambda rng: rng.uniform(min_value, max_value))

    class _Assumption(Exception):
        pass

    def assume(condition):
        if not condition:
            raise _Assumption()
        return True

    def given(**strategies):
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                n = getattr(wrapper, "_hyp_max_examples", None) or getattr(
                    fn, "_hyp_max_examples", _DEFAULT_MAX_EXAMPLES
                )
                seed = zlib.crc32(fn.__qualname__.encode())
                rng = random.Random(seed)
                for _ in range(n):
                    drawn = {k: s.draw(rng) for k, s in strategies.items()}
                    try:
                        fn(*args, **kwargs, **drawn)
                    except _Assumption:
                        continue

            # hide the drawn parameters from pytest's fixture resolution:
            # only the test's non-strategy parameters remain visible
            params = [
                prm
                for name, prm in inspect.signature(fn).parameters.items()
                if name not in strategies
            ]
            wrapper.__signature__ = inspect.Signature(params)
            del wrapper.__wrapped__
            return wrapper

        return deco

    def settings(max_examples=_DEFAULT_MAX_EXAMPLES, **_kw):
        def deco(fn):
            fn._hyp_max_examples = max_examples
            return fn

        return deco

    _hyp = types.ModuleType("hypothesis")
    _hyp.given = given
    _hyp.settings = settings
    _hyp.assume = assume
    _hyp.HealthCheck = types.SimpleNamespace(all=lambda: [])
    _st = types.ModuleType("hypothesis.strategies")
    _st.integers = integers
    _st.sampled_from = sampled_from
    _st.booleans = booleans
    _st.floats = floats
    _hyp.strategies = _st
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st


# ----------------------------------------------------------------------- #
# plan-cache isolation: the scheduling layer persists plans on disk
# (repro.core.plan_cache); tests must neither read a developer's warm cache
# nor leave entries behind, so the whole session runs against a tmp dir
# unless a test explicitly overrides REPRO_PLAN_CACHE_DIR itself.
# ----------------------------------------------------------------------- #
import pytest


@pytest.fixture(autouse=True, scope="session")
def _isolated_plan_cache(tmp_path_factory):
    import os

    prev = os.environ.get("REPRO_PLAN_CACHE_DIR")
    os.environ["REPRO_PLAN_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("plan_cache")
    )
    yield
    if prev is None:
        os.environ.pop("REPRO_PLAN_CACHE_DIR", None)
    else:
        os.environ["REPRO_PLAN_CACHE_DIR"] = prev


@pytest.fixture
def flash_on_cpu(monkeypatch):
    """Sends causal attention through the blocked kernel
    (``repro.kernels.attention``) on the CPU, as ``attention()`` does on the
    TPU, with the kernel run by the Pallas interpreter."""
    from repro.kernels import attention as flash

    monkeypatch.setattr(flash, "PLATFORM", "cpu")
    monkeypatch.setattr(
        flash, "causal_attention",
        functools.partial(flash.causal_attention, interpret=True),
    )
