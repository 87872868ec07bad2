"""CPU rehearsal of the ``train-fed`` runner at toy size: a whole run, and
a whole run with the timed path broken underneath."""

import pytest

from bench_helpers import run_toy

pytestmark = pytest.mark.integration


def test_toy_train_cell_is_correct_and_prints_the_contract_keys(capfd):
    result = run_toy("toy-resnet-fed", 3000000311)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"samples_per_s_per_chip", "setup_s"}
    assert result["metrics"]["samples_per_s_per_chip"]["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    captured = capfd.readouterr()
    for name in ("loss_rel", "grad_norm_rel", "delta_norm_rel",
                 "compiles_in_window", "feed_not_on_shm"):
        # each number beside its limit: as a fact, last in the result
        # line, and among the last lines of standard error
        assert f'"name": "{name}"' in captured.out
        assert set(result["compared"][name]) == {"value", "limit"}
        assert f"compared {name} " in captured.err
    assert captured.err.strip().splitlines()[-1].startswith(
        "compared " + list(result["compared"])[-1])


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    result = run_toy("toy-resnet-frozen", 312)
    assert result["correct"] is False
