import os
import random
import sys
import types

# Tests run single-device (the dry-run, and only the dry-run, forces 512
# placeholder devices in its own process — see launch/dryrun.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import pytest

jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(autouse=True)
def _release_compiled_programs():
    """Drop JAX's compiled programs after each test. XLA:CPU maps every
    kernel of a program into memory on its own, and an interpret-mode
    Pallas pipeline is hundreds of kernels; a test worker that kept every
    program alive would pass vm.max_map_count (65530) and abort."""
    yield
    jax.clear_caches()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: slow / interpret-mode Pallas tests (deselect with -m 'not slow')"
    )


# ---------------------------------------------------------------------------
# hypothesis fallback: the container may not ship hypothesis. The property
# tests only use integers()/sampled_from(), so a deterministic re-sampling
# stand-in preserves their coverage instead of dying at collection.
# ---------------------------------------------------------------------------

try:  # pragma: no cover - exercised only where hypothesis is installed
    import hypothesis  # noqa: F401
except ImportError:

    class _Strategy:
        def __init__(self, draw):
            self.draw = draw

    def _integers(min_value=0, max_value=2**31 - 1):
        return _Strategy(lambda rng: rng.randint(min_value, max_value))

    def _sampled_from(seq):
        choices = list(seq)
        return _Strategy(lambda rng: rng.choice(choices))

    def _settings(max_examples=10, deadline=None, **_):
        def deco(fn):
            fn._stub_max_examples = max_examples
            return fn

        return deco

    def _given(**strats):
        def deco(fn):
            def run():
                n = getattr(run, "_stub_max_examples", 10)
                rng = random.Random(0xC0FFEE)
                for _ in range(n):
                    drawn = {k: s.draw(rng) for k, s in strats.items()}
                    fn(**drawn)

            # zero-arg signature: the strategy kwargs must not look like
            # pytest fixtures (functools.wraps would re-expose them)
            run.__name__ = fn.__name__
            run.__doc__ = fn.__doc__
            run.__module__ = fn.__module__
            return run

        return deco

    _st = types.ModuleType("hypothesis.strategies")
    _st.integers = _integers
    _st.sampled_from = _sampled_from
    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given
    _hyp.settings = _settings
    _hyp.strategies = _st
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st
