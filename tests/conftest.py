"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests must see the
real single CPU device (subprocess tests set their own device count)."""
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np
import pytest

# hypothesis is a dev-only dependency (requirements-dev.txt): register the
# property-test profiles here, once, so every module shares them.  "ci"
# derandomizes (examples derived from the test function itself, no RNG, no
# example database) so tier-1 is bit-for-bit reproducible on CI; locally the
# default "dev" profile keeps randomized exploration.  Select explicitly
# with HYPOTHESIS_PROFILE=ci|dev.
try:
    from hypothesis import settings as _hyp_settings

    _hyp_settings.register_profile("dev", max_examples=25, deadline=None)
    _hyp_settings.register_profile("ci", max_examples=25, deadline=None,
                                   derandomize=True, print_blob=True)
    _hyp_settings.load_profile(os.environ.get(
        "HYPOTHESIS_PROFILE", "ci" if os.environ.get("CI") else "dev"))
except ImportError:                      # pragma: no cover - optional dep
    pass


def pytest_configure(config):
    # the two slowest 8-device mesh-parity tests carry this marker so a
    # local quick loop can skip them (`pytest -m "not slow"`); tier-1 CI
    # runs everything — the marker documents cost, it never gates coverage
    config.addinivalue_line(
        "markers",
        "slow: slowest mesh-parity tests; deselect locally with "
        '-m "not slow"')


@pytest.fixture(scope="module")
def rng():
    # one stream a test module: a module draws the same data whichever
    # modules a test worker ran before it
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.key(0)


@pytest.fixture(scope="session")
def op_name_scopes():
    """The path components of every op_name in lowered HLO text
    (``.as_text(debug_info=True)``), looking through transformation
    wrappers: ``vmap(transpose(jvp(temporal)))/while`` gives ``temporal``
    and ``while`` among others."""
    def scopes(text):
        out = set()
        for name in re.findall(r'loc\("([^"]*)"', text):
            out.update(re.split(r"[/()]", name))
        return out
    return scopes
