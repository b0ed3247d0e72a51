"""Tier-1 collects the yardstick's own tests: ``chipbench/tests/test_xtrace.py``
runs here as it stands (ROADMAP D2). One thin file for each of the
benchmark's test files, so that ``--dist loadfile`` spreads them."""

import pytest

pytest.register_assert_rewrite("chipbench.tests.test_xtrace")

from chipbench.tests.test_xtrace import *  # noqa: E402,F401,F403
