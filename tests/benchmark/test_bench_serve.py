"""CPU rehearsal of the ``serve-closed`` runner at toy size: a whole run, a
whole run with served tokens altered where they are produced, and the
per-layer metrics that read the program's counters."""

import pytest

from bench_helpers import run_toy

pytestmark = pytest.mark.integration


def test_toy_serve_cell_is_correct_and_prints_the_contract_keys(capfd):
    result = run_toy("toy-gpt-batch-decode", 3000000314)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "gap_p95_ms",
                                      "setup_s"}
    out = capfd.readouterr().out
    for name in ("served_gap_sigmas", "served_gap_mean_sigmas",
                 "streams_of_wrong_length", "failed_requests",
                 "compiles_in_window"):
        assert f'"name": "{name}"' in out
    assert '"gap_quantiles_ms"' in out


def test_altered_tokens_are_not_correct_and_counters_feed_the_layers():
    result = run_toy("toy-gpt-altered", 315, trace=1)
    assert result["correct"] is False
    for name in ("decode_rows_per_step", "prefill_step_share.serve",
                 "step_period_ms.serve", "ttft_p50_ms.closed", "warmup_s"):
        assert result["metrics"][name]["value"] > 0
    assert "tokens_per_s" not in result["metrics"]
