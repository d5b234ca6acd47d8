"""Property-based tier (hypothesis) for the serving control plane.

Two contracts checked over generated inputs instead of hand-picked
cases:

* the **SLO router never overrides Eq. 23/24**: for memory-bound
  advice the decided engine is the vector engine at every queue
  depth/headroom, and the width trajectory stays inside
  ``[1, max_width]`` moving only by factors of two,
* the serving **percentile estimator matches numpy.percentile**
  exactly (the 'reproducible with stock tooling' contract of
  ``repro.serving.metrics``).

The tier is marked ``property`` and self-skips when hypothesis is not
installed (it is a dev extra, not a runtime dependency).
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")

from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.serving.batcher import KernelBatchExecutor  # noqa: E402
from repro.serving.metrics import percentile  # noqa: E402
from repro.serving.router import SLORouter  # noqa: E402

pytestmark = pytest.mark.property


@settings(max_examples=50, deadline=None)
@given(st.lists(st.tuples(st.integers(min_value=0, max_value=64),
                          st.floats(min_value=0.0, max_value=200.0,
                                    allow_nan=False,
                                    allow_infinity=False)),
                min_size=1, max_size=40),
       st.sampled_from(["scale", "axpy", "triad"]),
       st.sampled_from([4096, 65536, 1 << 20]))
def test_router_never_violates_ceiling(signals, kernel, size):
    """At every queue depth and SLO headroom the router records the
    Advice engine unchanged — memory-bound work stays on the vector
    engine (Eq. 23/24 as an online invariant, §6 under load) — and
    the width walks [1, max_width] by factors of two."""
    advice = KernelBatchExecutor(engine="auto").advice_for(
        kernel, size, "float32")
    router = SLORouter(slo_ms=50.0, max_width=4)
    prev = router.width
    for i, (depth, wait_ms) in enumerate(signals):
        decision = router.decide(clock_s=0.05 * i, engine=advice.engine,
                                 queue_depth=depth,
                                 oldest_wait_ms=wait_ms)
        if advice.memory_bound:
            assert decision.engine == "vector"
        assert 1 <= decision.width <= 4
        assert decision.width in (prev, prev * 2, prev // 2)
        prev = decision.width


@settings(max_examples=100, deadline=None)
@given(st.lists(st.floats(min_value=-1e6, max_value=1e6,
                          allow_nan=False, allow_infinity=False),
                min_size=1, max_size=64),
       st.floats(min_value=0.0, max_value=100.0,
                 allow_nan=False, allow_infinity=False))
def test_percentile_matches_numpy(values, q):
    """Bit-for-bit agreement with numpy.percentile's default linear
    interpolation — the published tail numbers reproduce with stock
    tooling."""
    ours = percentile(values, q)
    theirs = float(np.percentile(np.asarray(values, dtype=np.float64), q))
    assert ours == theirs
