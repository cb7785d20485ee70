import os

# Tests run against the single real CPU device (the dry-run — and ONLY the
# dry-run — forces 512 host devices via its own module-level XLA_FLAGS).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _same(a, b) -> bool:
    """Exact equality of record values, NaN equal to NaN, at any depth."""
    if isinstance(a, dict):
        return (isinstance(b, dict) and a.keys() == b.keys()
                and all(_same(a[k], b[k]) for k in a))
    if isinstance(a, (list, tuple)):
        return (isinstance(b, (list, tuple)) and len(a) == len(b)
                and all(_same(x, y) for x, y in zip(a, b)))
    if isinstance(a, float) and a != a:
        return isinstance(b, float) and b != b
    return a == b


def _assert_same_run(engine, server) -> None:
    """``engine`` ran the rounds ``server`` (a sequential ``Server`` in
    the same process) ran, bit for bit: every key of every history
    record (an engine may add keys of its own, such as ``spec_hit``) and
    every params leaf."""
    import jax
    assert len(engine.history) == len(server.history)
    for got, want in zip(engine.history, server.history):
        for k, v in want.items():
            assert k in got and _same(got[k], v), (got["round"], k)
    a, b = engine.global_params, server.global_params
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture
def same_run():
    """The same-host engine-equality check, :func:`_assert_same_run`."""
    return _assert_same_run
