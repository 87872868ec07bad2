"""CPU rehearsal of the ``serve-closed`` runner at toy size: a whole run, a
whole run with served tokens altered where they are produced, and the
per-layer metrics that read the program's counters."""

import json

import pytest

from bench_helpers import run_toy

pytestmark = pytest.mark.integration


def test_toy_serve_cell_is_correct_and_prints_the_contract_keys(capfd):
    result = run_toy("toy-gpt-batch-decode", 3000000314)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "gap_p99_ms",
                                      "setup_s"}
    captured = capfd.readouterr()
    out = captured.out
    compared = ("served_gap_sigmas", "served_gap_mean_sigmas",
                "streams_finished_in_window_missing",
                "streams_of_wrong_length", "failed_requests",
                "compiles_in_window")
    for name in compared:
        assert f'"name": "{name}"' in out
    # every number compared, beside its limit: last in the result line and
    # the last lines of standard error
    assert tuple(result["compared"]) == compared
    assert all(set(v) == {"value", "limit"}
               for v in result["compared"].values())
    assert [line.split()[1] for line in
            captured.err.strip().splitlines()[-len(compared):]] \
        == list(compared)
    window = next(json.loads(line) for line in out.splitlines()
                  if line.startswith('{"fact": "serve window"'))
    assert set(window["gap_quantiles_ms"]) == {"50", "75", "90", "92", "95",
                                               "98", "99"}
    assert result["metrics"]["gap_p99_ms"]["value"] \
        == window["gap_quantiles_ms"]["99"]
    assert window["turns_per_s"] == pytest.approx(
        window["counters"]["tfos_replica_steps_total"] / window["seconds"])
    assert window["caller_turnaround_ms"]["n"] > 0
    assert 0 <= window["caller_turnaround_ms"]["p50"] \
        <= window["caller_turnaround_ms"]["p95"]
    assert window["host_turn_ms"] > 0 and "decode_dispatch" \
        in window["phase_ms_per_step"]
    tail = next(json.loads(line) for line in out.splitlines()
                if line.startswith('{"fact": "tail"'))
    assert tail["metric"] == "gap_p99_ms" and tail["percentile"] == 99
    assert tail["inside_a_mode"] in (True, False)


def test_altered_tokens_are_not_correct_and_counters_feed_the_layers():
    result = run_toy("toy-gpt-altered", 315, trace=1)
    assert result["correct"] is False
    for name in ("decode_rows_per_step", "prefill_step_share.serve",
                 "step_period_ms.serve", "ttft_p50_ms.closed", "warmup_s",
                 "host_turn_ms.serve"):
        assert result["metrics"][name]["value"] > 0
    assert "tokens_per_s" not in result["metrics"]
