"""Tier-1 collects the yardstick's own tests:
``chipbench/tests/test_token_parts.py`` (PR 45: the token cells' split by
mixer and part, the two shares' least work, the table) runs here as it stands
(ROADMAP D2), like its neighbours ``tests/test_chipbench_*.py``."""

import pytest

pytest.register_assert_rewrite("chipbench.tests.test_token_parts")

from chipbench.tests.test_token_parts import *  # noqa: E402,F401,F403
