"""``tests/test_decode_ahead.py``'s cases on the short-convolution + K/V toy
(LFM2's pattern)."""

import pytest

from test_decode_ahead import (  # noqa: F401 — the cases, collected here too
    _toy,
    test_a_pool_too_small_for_a_steps_growth_reads_first,
    test_a_roomy_pool_decodes_ahead_on_every_decode_step,
    test_a_stop_token_wastes_one_row_and_nothing_else,
    test_the_decode_program_feeds_an_idle_row_the_pad_token,
)


@pytest.fixture(scope="module", params=["conv_kv"])
def toy(request):
    return _toy(request.param)
