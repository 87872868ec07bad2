"""The three per-layer metrics PR 25 added, on synthetic runs: the decode
and the prefill program read by NAME from the reduced trace, and the share
of idle-gap seconds that no ``tfos/`` span of the program covers; and the
readers of the program's phase clocks and of the step's share of the
chip's peak, and a cell's own names for its metrics (PR 38)."""

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


def test_no_gaps_is_zero_and_a_session_set_aside_still_reads():
    """The share is of gap seconds: a session whose idle share was set
    aside for the window's (five of six traced sides in the ledger before
    PR 38) still says which spans covered its gaps."""
    assert _read("unattributed_gap_share.serve",
                 _run(trace={"idle_gaps": []})) == 0.0
    differ = _run(idle={"differ": True, "value": 0.7})
    assert _read("unattributed_gap_share.serve", differ) \
        == pytest.approx(10.0)
    # a reduced trace from before the gaps were kept reads nothing
    old = _run()
    del old["trace"]["idle_gaps"]
    assert _read("unattributed_gap_share.serve", old) is None


def test_a_set_aside_serve_session_keeps_its_gaps_in_the_breakdown(capsys):
    import json

    from benchmark import run as run_mod

    idle = {"differ": True, "value": 7.0, "busy_s": 41.8, "window_s": 45.0}
    account = dict(
        _run(idle=idle, trace={"device_ops": [["jit_tfos_decode/x", 0.01]]}),
        correct=True, attempted=3, failed=0, cell={"config_data": {}},
        counters={}, window_s=45.0, warmup_s=30.0, ttft_ms=[600.0], spans={},
        report={}, compared=[{"name": "failed_requests", "value": 0.0,
                              "limit": 0.0}],
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1})
    line = json.loads(run_mod.result_of(account, trace=1))
    assert line["breakdown"]["idle_gaps"] == account["trace"]["idle_gaps"]
    assert line["session_set_aside"] is True
    assert (line["device"]["busy_s"], line["device"]["window_s"]) \
        == (41.8, 45.0)
    # what was compared comes last in the line, and last on standard error
    assert list(line)[-1] == "compared"
    assert line["compared"] == {"failed_requests": {"value": 0.0,
                                                    "limit": 0.0}}
    assert capsys.readouterr().err.strip().splitlines()[-1] \
        == "compared failed_requests 0.0 limit 0.0"
    sound = json.loads(run_mod.result_of(
        dict(account, idle=dict(idle, differ=False)), trace=1))
    assert "session_set_aside" not in sound


@pytest.mark.parametrize("name", NEW)
def test_untraced_and_train_runs_read_nothing(name):
    assert _read(name, dict(_run(), trace=None, idle=None)) is None
    train = {"kind": "train-fed", "idle": {"differ": False},
             "trace": {"programs": {"jit_tfos_train_step":
                                    {"runs": 10, "seconds": 1.26}},
                       "idle_gaps": [["bench/sync", 0.001]]}}
    assert _read(name, train) is None


STEADY_CELLS = ("lfm2-8b-a1b-batch-decode", "brumby-14b-batch-decode")


@pytest.mark.parametrize("name", NEW + ("host_turn_ms.serve",
                                        "step_mfu.serve"))
def test_manifest_declares_the_metric_for_every_serve_cell(name):
    """Present with its fields in every serve cell, under a layer other
    entries name too, moving a metric those cells report: once for the two
    steady cells, and once, under the name the cell's own file gives it,
    for ``gpt2xl-batch-decode``, whose end-to-end metrics have names and
    bounds of their own.  (Until PR 38 this pinned PR 25's three as the
    list's LAST three with one cell alone, which left later PRs no place
    to append an entry.)"""
    manifest = harness.manifest()
    own = harness.load_cell(CELL)["metric_names"][name]
    for listed, cells in ((name, STEADY_CELLS), (own, (CELL,))):
        entry, = [m for m in manifest["per_layer"] if m["name"] == listed]
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert set(entry["workloads"]) == set(cells)
        moved, = [m for m in manifest["end_to_end"]
                  if m["name"] == entry["moves"]]
        assert set(entry["workloads"]) <= set(moved["workloads"])
        assert entry["layer"] in {m["layer"] for m in manifest["per_layer"]
                                  if m is not entry}


def _own_names():
    """(cell, the harness's name, the cell's own) of every listed cell."""
    return [(w["name"], name, own) for w in harness.manifest()["workloads"]
            for name, own in harness.load_cell(w["name"]).get(
                "metric_names", {}).items()]


def test_every_reader_has_an_entry_and_every_entry_a_reader():
    """An entry's reader is the file of its name, or, for a name that a
    listed cell's ``metric_names`` gives a metric, that metric's file."""
    import os

    from bench_helpers import ROOT

    readers = {f[:-3] for f in os.listdir(
        os.path.join(ROOT, "benchmark", "layer_metrics")) if f.endswith(".py")}
    manifest = harness.manifest()
    entries = {m["name"] for m in manifest["per_layer"]}
    reported = {m["name"] for m in manifest["end_to_end"]}
    stands_for = {own: name for _, name, own in _own_names()}
    assert len(stands_for) == len(_own_names())
    assert not set(stands_for) & readers
    assert set(stands_for) <= entries | reported
    assert entries - set(stands_for) == readers
    assert {stands_for[n] for n in entries & set(stands_for)} <= readers
    assert {m["moves"] for m in manifest["per_layer"]} <= reported
    assert "gap_p95_ms" not in reported and "gap_p99_ms" in reported


@pytest.mark.parametrize("cell,name,own", _own_names())
def test_a_cells_own_name_is_the_metric_it_stands_for(cell, name, own):
    """The cell's alone, the other entry without the cell, and the same
    unit, direction, source and (per layer) layer and reader."""
    manifest = harness.manifest()
    section = "end_to_end" if name in {
        m["name"] for m in manifest["end_to_end"]} else "per_layer"
    entries = {m["name"]: m for m in manifest[section]}
    assert entries[own]["workloads"] == [cell]
    assert cell not in entries[name]["workloads"]
    same = ("unit", "better", "source") + (
        ("layer",) if section == "per_layer" else ())
    assert [entries[own][k] for k in same] == [entries[name][k] for k in same]
    if section == "per_layer":
        assert harness.reader_of(own, harness.load_cell(cell)) \
            is harness.load_module("layer_metrics", name)
        moved = {o: n for _, n, o in _own_names()}[entries[own]["moves"]]
        assert moved == entries[name]["moves"]
    else:       # its own bound, no tighter than the others'
        assert entries[own]["bound"] >= entries[name]["bound"]


def test_a_cell_reports_under_its_own_names_and_the_others_do_not():
    cell = harness.load_cell(CELL)
    values = {"tokens_per_s": 1740.0, "gap_p99_ms": 25.0, "setup_s": 66.0}
    got = harness.pick_metrics(harness.reported_as(values, cell),
                               harness.declared_for("end_to_end", CELL))
    assert {k: v["value"] for k, v in got.items()} == {
        "tokens_per_s.gpt2xl": 1740.0, "gap_p99_ms.gpt2xl": 25.0,
        "setup_s": 66.0}
    steady = harness.load_cell(STEADY_CELLS[0])
    got = harness.pick_metrics(
        harness.reported_as(values, steady),
        harness.declared_for("end_to_end", STEADY_CELLS[0]))
    assert set(got) == {"tokens_per_s", "gap_p99_ms", "setup_s"}
    # the bounds: the steady cells' own, not the widest cell's
    bound = {m["name"]: m["bound"] for m in harness.manifest()["end_to_end"]}
    assert bound["tokens_per_s"] <= 0.03 and bound["gap_p99_ms"] <= 0.03
    # per layer: the cell's entries, read by the readers they stand for
    run = dict(_counted(**{"phase_seconds.decode_dispatch": 13.6}),
               cell=cell, window_s=45.0, warmup_s=30.0, ttft_ms=[40.0],
               steps=4000.0)
    layers = harness.layer_metrics(run)
    assert layers["host_turn_ms.gpt2xl"] == pytest.approx(3.4)
    assert layers["decode_device_ms.gpt2xl"] == pytest.approx(235.0)
    assert not {"host_turn_ms.serve", "decode_device_ms.serve"} & set(layers)
    # a rehearsal cell gets every entry and skips the names that are
    # another cell's
    toy = dict(run, cell=harness.load_cell("toy-gpt-batch-decode"))
    layers = harness.layer_metrics(toy)
    assert "host_turn_ms.serve" in layers
    assert "host_turn_ms.gpt2xl" not in layers


def _counted(**counters):
    base = {"tfos_replica_steps_total": 4000.0,
            "tfos_replica_tokens_total": 63600.0,
            "tfos_replica_decode_dispatches_total": 4000.0,
            "tfos_replica_prefill_dispatches_total": 90.0}
    return dict(_run(), counters=dict(base, **counters))


def test_host_turn_is_the_phase_clocks_without_the_waits():
    run = _counted(**{"phase_seconds.decode_dispatch": 13.6,
                      "phase_seconds.intake": 4.8,
                      "phase_seconds.flush": 2.0,
                      "phase_seconds.decode_fetch": 20.0,
                      "phase_seconds.prefill_fetch": 6.0,
                      "phase_seconds.idle": 0.4})
    assert _read("host_turn_ms.serve", run) == pytest.approx(5.1)
    assert _read("host_turn_ms.serve", _counted()) is None   # no clocks read
    assert _read("host_turn_ms.serve", dict(run, kind="train-fed")) is None


def test_step_mfu_reads_the_configurations_own_step_and_is_never_zero(capsys):
    cell = harness.load_cell(CELL)
    run = dict(_counted(), cell=cell, mean_context_tokens=540.0,
               device={"kind": "TPU v5 lite"})
    run["trace"]["programs"]["jit_tfos_decode"] = {"runs": 24,
                                                   "seconds": 0.228}
    share = _read("step_mfu.serve", run)
    # 2 x 1.5555e9 matmul weights x 15.9 rows + the attention's products
    # over 8,586 live tokens, over 197 TFLOP/s x 9.5 ms
    assert share == pytest.approx(2.92, abs=0.1)
    # 3.1 GB of weights and 2.6 GB of live K/V at 819 GB/s: 7.0 of 9.5 ms
    roof = _read("decode_step_roofline", run)
    assert roof == pytest.approx(73.9, abs=1.0) and share < roof
    # a cell whose declared readers count no step, an untraced run, a
    # train run
    bare = dict(run, cell=dict(harness.load_cell("resnet50-fed"),
                               config_data=cell["config_data"]))
    assert _read("step_mfu.serve", bare) is None
    assert _read("step_mfu.serve", dict(run, trace=None)) is None
    assert _read("step_mfu.train", run) is None
    train = {"kind": "train-fed", "cell": harness.load_cell("resnet50-fed"),
             "report": {"global_batch": 256}, "device": {"kind": "TPU v5 lite"},
             "trace": {"main_program": "jit_tfos_train_step", "programs": {
                 "jit_tfos_train_step": {"runs": 10, "seconds": 1.2649}}}}
    # 256 x 3 x 2 x 4.09e9 operations in 126.49 ms of a 197 TFLOP/s chip
    assert _read("step_mfu.train", train) == pytest.approx(25.2, abs=0.3)
    assert _read("step_mfu.serve", train) is None
    capsys.readouterr()


def test_layer_metrics_carries_the_new_values_into_a_result_line():
    run = _run(cell={"config_data": {}}, counters={}, window_s=45.0,
               warmup_s=30.0, ttft_ms=[600.0], spans={}, report={},
               device={"kind": "TPU v5 lite"})
    values = harness.layer_metrics(run)
    assert values["decode_device_ms.serve"] == pytest.approx(235.0)
    assert values["prefill_device_ms.serve"] == pytest.approx(126.0)
    assert values["unattributed_gap_share.serve"] == pytest.approx(10.0)
