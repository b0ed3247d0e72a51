"""Tier-1 collects the yardstick's own tests:
``chipbench/tests/test_attention_kernel_metrics.py`` (PR 40: the attention
kernels' readers of the Qwen3-Next and Nemotron-H cells) runs here as it
stands (ROADMAP D2), like its neighbours ``tests/test_chipbench_*.py``."""

import pytest

pytest.register_assert_rewrite("chipbench.tests.test_attention_kernel_metrics")

from chipbench.tests.test_attention_kernel_metrics import *  # noqa: E402,F401,F403
