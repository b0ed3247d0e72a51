"""Tier-1 collects the yardstick's own tests: ``chipbench/tests/test_step_classes.py``
runs here as it stands (ROADMAP D2)."""

import pytest

pytest.register_assert_rewrite("chipbench.tests.test_step_classes")

from chipbench.tests.test_step_classes import *  # noqa: E402,F401,F403
