"""The three per-layer metrics PR 25 added, on synthetic runs: the decode
and the prefill program read by NAME from the reduced trace, and the share
of idle-gap seconds that no ``tfos/`` span of the program covers."""

import pytest

from bench_helpers import ROOT  # noqa: F401  (puts the checkout on the path)

from benchmark import harness

CELL = "gpt2xl-batch-decode"
NEW = ("decode_device_ms.serve", "prefill_device_ms.serve",
       "unattributed_gap_share.serve")


def _run(**over):
    trace = {"main_program": "jit_tfos_decode", "steps": 24,
             "programs": {
                 "jit_tfos_decode": {"runs": 24, "seconds": 5.64},
                 "jit_tfos_prefill": {"runs": 3, "seconds": 0.378},
                 "jit_tfos_kv_park": {"runs": 3, "seconds": 0.0003}},
             "idle_gaps": [["tfos/serve/flush", 0.15],
                           ["tfos/batcher/emit", 0.03],
                           ["host/unattributed", 0.02]]}
    trace.update(over.pop("trace", {}))
    run = {"kind": "serve-closed", "trace": trace,
           "idle": {"differ": False, "value": 3.1}}
    run.update(over)
    return run


def _read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


def test_decode_program_is_read_by_name_not_by_rank():
    assert _read("decode_device_ms.serve", _run()) == pytest.approx(235.0)
    # the prefill holding the device longest changes nothing
    run = _run(trace={"main_program": "jit_tfos_prefill"})
    assert _read("decode_device_ms.serve", run) == pytest.approx(235.0)


def test_sampled_and_block_decode_programs_count_with_the_greedy_one():
    run = _run(trace={"programs": {
        "jit_tfos_decode": {"runs": 10, "seconds": 2.0},
        "jit_tfos_decode_sampled": {"runs": 5, "seconds": 1.3},
        "jit_tfos_decode_block": {"runs": 5, "seconds": 0.7}}})
    assert _read("decode_device_ms.serve", run) == pytest.approx(200.0)


def test_prefill_program_is_read_by_name():
    assert _read("prefill_device_ms.serve", _run()) == pytest.approx(126.0)


@pytest.mark.parametrize("name", NEW[:2])
def test_a_program_without_the_names_reads_nothing(name):
    """The parent commit names its programs ``jit_step_greedy`` and
    ``jit_pfinal_fn``: the readers find nothing and do not raise."""
    run = _run(trace={"main_program": "jit_step_greedy", "programs": {
        "jit_step_greedy": {"runs": 24, "seconds": 5.64},
        "jit_pfinal_fn": {"runs": 3, "seconds": 0.378}}})
    assert _read(name, run) is None


def test_traced_steps_without_an_admission_have_no_prefill_time():
    run = _run(trace={"programs": {
        "jit_tfos_decode": {"runs": 24, "seconds": 5.64}}})
    assert _read("prefill_device_ms.serve", run) is None
    assert _read("decode_device_ms.serve", run) == pytest.approx(235.0)


def test_unattributed_share_of_the_gap_seconds():
    assert _read("unattributed_gap_share.serve", _run()) \
        == pytest.approx(10.0)
    all_named = _run(trace={"idle_gaps": [["tfos/serve/flush", 0.2]]})
    assert _read("unattributed_gap_share.serve", all_named) == 0.0
    parent = _run(trace={"idle_gaps": [["host/unattributed", 0.1998]]})
    assert _read("unattributed_gap_share.serve", parent) \
        == pytest.approx(100.0)


def test_no_gaps_is_zero_and_a_session_set_aside_is_nothing():
    assert _read("unattributed_gap_share.serve",
                 _run(trace={"idle_gaps": []})) == 0.0
    differ = _run(idle={"differ": True, "value": 0.7})
    assert _read("unattributed_gap_share.serve", differ) is None


@pytest.mark.parametrize("name", NEW)
def test_untraced_and_train_runs_read_nothing(name):
    assert _read(name, dict(_run(), trace=None, idle=None)) is None
    train = {"kind": "train-fed", "idle": {"differ": False},
             "trace": {"programs": {"jit_tfos_train_step":
                                    {"runs": 10, "seconds": 1.26}},
                       "idle_gaps": [["bench/sync", 0.001]]}}
    assert _read(name, train) is None


@pytest.mark.parametrize("name", NEW)
def test_manifest_declares_the_metric_for_the_serve_cell_alone(name):
    manifest = harness.manifest()
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL]
    assert entry in manifest["per_layer"][-3:]      # appended, not inserted
    moved, = [m for m in manifest["end_to_end"]
              if m["name"] == entry["moves"]]
    assert CELL in moved["workloads"]
    assert entry["layer"] in {m["layer"] for m in manifest["per_layer"][:-3]}


def test_layer_metrics_carries_the_new_values_into_a_result_line():
    run = _run(cell={"config_data": {}}, counters={}, window_s=45.0,
               warmup_s=30.0, ttft_ms=[600.0], spans={}, report={},
               device={"kind": "TPU v5 lite"})
    values = harness.layer_metrics(run)
    assert values["decode_device_ms.serve"] == pytest.approx(235.0)
    assert values["prefill_device_ms.serve"] == pytest.approx(126.0)
    assert values["unattributed_gap_share.serve"] == pytest.approx(10.0)
