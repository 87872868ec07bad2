"""A four-device data-parallel cell added as FILES ONLY
(``workloads/toy-resnet-dp4.json``: ``"chips": 4``) runs on four virtual
devices: the proof that ``resnet50-dp4`` needs no edit of the harness."""

import pytest

from bench_helpers import run_toy

pytestmark = pytest.mark.integration


def test_dp4_cell_runs_from_files_alone(capfd):
    result = run_toy("toy-resnet-dp4", 313, trace=1)
    assert result["correct"] is True
    assert result["device"]["count"] == 4
    # a traced run reports per-layer metrics; on the CPU there is no
    # device plane, so the readers of the trace return nothing
    assert "warmup_s" in result["metrics"]
    assert "feed_wait_ms.train" in result["metrics"]
    assert "samples_per_s_per_chip" not in result["metrics"]
    assert '"name": "params_not_on_every_chip", "value": 0.0' \
        in capfd.readouterr().out
