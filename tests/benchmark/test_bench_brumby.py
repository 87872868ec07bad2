"""CPU rehearsal of the ``brumby-14b-batch-decode`` cell's plumbing at toy
size (``toy-brumby`` / ``toy-brumby-batch-decode``, files in no manifest):
the adapter boots with no K/V pool and its counters move, the three new
readers read a stored reduced trace, ``trace_kernels`` sums a kernel by its
operation name, the shape functions count the cut, the configuration keeps
the catalog's numbers, the request list keeps its separation, and the fp8
control fails the logit comparison."""

import json
import os

import numpy as np
import pytest

from bench_helpers import ROOT, run_toy

from benchmark import harness, shapes_brumby, trace_kernels

CELL = "brumby-14b-batch-decode"
NEW_METRICS = ("retention_step_roofline", "retention_decode_step_roofline",
               "state_bytes_per_step.serve")


@pytest.mark.integration
def test_toy_brumby_cell_boots_and_its_counters_move(capfd):
    result = run_toy("toy-brumby-batch-decode", 3000000329, trace=1,
                     control=True)
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"]["decode_rows_per_step"]["value"] > 0
    out = capfd.readouterr().out
    said = [json.loads(line) for line in out.splitlines()
            if line.startswith('{"fact"')]
    window = next(s for s in said if s["fact"] == "serve window")
    for name in ("tfos_replica_state_rows_seated_total",
                 "tfos_replica_state_bytes_moved_total",
                 "tfos_replica_decode_ahead_dispatches_total"):
        assert window["counters"][name] > 0, name
    # the program's own account of the state it moved, per decode step, is
    # the whole batch's state three times over off the TPU (the jax.numpy
    # arithmetic): 4 rows x 3 layers x 2 heads x 17 x 192 float32
    state = harness.load_module("layer_metrics", "state_bytes_per_step.serve")
    got = state.read({"kind": "serve-closed", "counters": window["counters"],
                      "cell": harness.load_cell("toy-brumby-batch-decode")})
    assert got == pytest.approx(3 * 4 * 3 * 2 * 17 * 192 * 4 / 1e9)
    # the three readers are in the manifest for this cell alone (PR 38);
    # on the CPU only the counter's reader finds something to read
    listed = {m["name"]: m for m in harness.manifest()["per_layer"]}
    for name in NEW_METRICS:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "tokens_per_s"
    assert result["metrics"]["state_bytes_per_step.serve"]["value"] \
        == pytest.approx(got)
    assert result["metrics"]["host_turn_ms.serve"]["value"] > 0
    assert not set(NEW_METRICS[:2]) & set(result["metrics"])
    # the fp8 control fails the comparison the sound streams pass
    control = next(s for s in said if s["fact"] == "control")
    limits = harness.load_cell("toy-brumby-batch-decode")[
        "config_data"]["limits"]
    assert control["served_gap_mean_sigmas"] \
        > limits["served_gap_mean_sigmas"]
    assert control["served_gap_sigmas"] > limits["served_gap_sigmas"]


def _stored_run(cell=CELL):
    """What the runner hands the readers, with a reduced trace as the
    observer stores it: 24 decode runs of 30 ms, 8 kernel calls a run."""
    cell = harness.load_cell(cell)
    steps, admissions = 1500.0, 50.0
    return {
        "kind": "serve-closed", "cell": cell,
        "device": {"kind": "TPU v5 lite"},
        "counters": {
            "tfos_replica_steps_total": steps,
            "tfos_replica_tokens_total": 16 * steps - 20 + admissions,
            "tfos_replica_decode_dispatches_total": steps,
            "tfos_replica_prefill_dispatches_total": admissions,
            "tfos_replica_state_rows_seated_total": admissions,
            "tfos_replica_state_bytes_moved_total":
                steps * 2 * 16 * 8 * 8 * 129 * 8704 * 4},
        "trace": {
            "main_program": "jit_tfos_decode", "steps": 24,
            "programs": {"jit_tfos_decode": {"runs": 24, "seconds": 0.72},
                         "jit_tfos_prefill": {"runs": 1, "seconds": 0.08}},
            "kernels": {"jit_tfos_decode": {
                "runs": 24, "seconds": 0.72,
                "kernels": {"tfos_retention_step": {"seconds": 0.48,
                                                    "calls": 192}},
                "scopes": {"ret/step": 0.5, "ret/qkvg": 0.06,
                           "ret/out": 0.05, "ret/qk_norm": 0.002}},
                "jit_tfos_prefill": {
                    "runs": 1, "seconds": 0.08, "kernels": {},
                    "scopes": {"ret/chunk": 0.05, "ret/qkvg": 0.004}}}}}


def test_the_new_readers_read_a_stored_reduced_trace(capsys):
    run = _stored_run()
    got = {name: harness.load_module("layer_metrics", name).read(run)
           for name in NEW_METRICS}
    # 8.72 GB of state at 819 GB/s = 10.65 ms of the kernels' 20 ms a run
    assert got["retention_step_roofline"] == pytest.approx(53.2, abs=0.5)
    # 15.57 GB = 19.0 ms of a 30 ms step
    assert got["retention_decode_step_roofline"] == pytest.approx(63.3,
                                                                  abs=0.5)
    # the program's 8704 features and all 16 rows: 9.20 GB a step
    assert got["state_bytes_per_step.serve"] == pytest.approx(9.198,
                                                              abs=0.01)
    said = [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]
    state, = [s for s in said if s["fact"] == "state bytes"]
    assert state["over_least"] == pytest.approx(1.055, abs=0.003)
    roofs = {s["metric"]: s for s in said if s["fact"] == "roofline"}
    assert set(roofs) == set(NEW_METRICS[:2])
    assert all(s["bound"] == "memory" and
               s["rows"] == pytest.approx(16 - 20 / 1500) for s in
               roofs.values())
    assert roofs["retention_step_roofline"]["calls_per_run"] == 8
    by_scope, = [s for s in said if s["fact"] == "decode device time by scope"]
    assert list(by_scope["ms_per_run"])[0] == "ret/step"
    prefill, = [s for s in said
                if s["fact"] == "prefill device time by scope"]
    assert prefill["ms_per_run"]["ret/chunk"] == pytest.approx(50.0)
    # a session that opened inside a run counts the run and holds only its
    # last two calls: the share is of the mean call, and does not rise
    decode = run["trace"]["kernels"]["jit_tfos_decode"]
    decode["runs"] = 25
    decode["kernels"]["tfos_retention_step"] = {"seconds": 0.485,
                                                "calls": 194}
    assert harness.load_module("layer_metrics", NEW_METRICS[0]).read(run) \
        == pytest.approx(got["retention_step_roofline"])


@pytest.mark.parametrize("strip", ["counters", "kernels", "trace", "config"])
def test_the_new_readers_return_nothing_where_there_is_nothing_to_read(
        strip):
    """A program from before the counter and the kernel (the parent of the
    PR that added them), an untraced run, a configuration without
    retention layers: nothing is read and nothing is raised."""
    run = _stored_run()
    if strip == "counters":
        run["counters"] = {k: v for k, v in run["counters"].items()
                           if "state_" not in k}
    elif strip == "kernels":
        del run["trace"]["kernels"]
    elif strip == "trace":
        run["trace"] = None
    else:
        run["cell"] = harness.load_cell("gpt2xl-batch-decode")
        del run["counters"]["tfos_replica_state_bytes_moved_total"]
    got = {name: harness.load_module("layer_metrics", name).read(run)
           for name in NEW_METRICS}
    if strip in ("counters", "config"):
        assert got == dict.fromkeys(NEW_METRICS)
    elif strip == "kernels":
        assert got["retention_step_roofline"] is None
        assert got["retention_decode_step_roofline"] is not None
    else:
        assert got["retention_step_roofline"] is None
        assert got["retention_decode_step_roofline"] is None
        assert got["state_bytes_per_step.serve"] is not None


def test_trace_kernels_sums_a_kernel_by_its_operation_name():
    """A built trace: two runs of the decode program, in each two kernel
    calls and one fusion under ``ret/qkvg``; one stray operation outside
    any run."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].name = "tf_op"
    names = {1: ("jit_tfos_decode(123)", ""),
             2: ("%tfos_retention_step.3 = f32[16,8,128,8704] custom-call()",
                 "jit(tfos_decode)/GPT/layer_0/ret/step/pallas_call"),
             3: ("%fusion.7 = bf16[16,5120] fusion()",
                 "jit(tfos_decode)/GPT/layer_0/ret/qkvg/dot_general"),
             4: ("%fusion.9 = f32[16] fusion()",
                 "jit(tfos_decode)/GPT/lm_head/dot_general")}
    for i, (name, tf_op) in names.items():
        md = plane.event_metadata[i]
        md.name = name
        if tf_op:
            stat = md.stats.add(metadata_id=1)
            stat.str_value = tf_op
    modules = plane.lines.add(name="XLA Modules", timestamp_ns=0)
    ops = plane.lines.add(name="XLA Ops", timestamp_ns=0)
    for start in (0, 100_000_000):                       # picoseconds
        modules.events.add(metadata_id=1, offset_ps=start,
                           duration_ps=50_000_000)
        for k, (mid, dur) in enumerate(((2, 10_000_000), (3, 2_000_000),
                                        (2, 11_000_000), (4, 1_000_000))):
            ops.events.add(metadata_id=mid, offset_ps=start + k * 12_000_000,
                           duration_ps=dur)
    ops.events.add(metadata_id=2, offset_ps=80_000_000, duration_ps=5_000_000)
    got = trace_kernels.reduce_space(space)["jit_tfos_decode"]
    assert got["runs"] == 2
    assert got["kernels"]["tfos_retention_step"]["calls"] == 4
    assert got["kernels"]["tfos_retention_step"]["seconds"] \
        == pytest.approx(42e-6)
    assert got["scopes"] == {"ret/step": pytest.approx(42e-6),
                             "ret/qkvg": pytest.approx(4e-6)}
    # a trace with neither a kernel nor a scope of the list reads nothing
    for e in list(ops.events):
        e.metadata_id = 4
    assert trace_kernels.reduce_space(space) is None


def test_shapes_count_the_cut_as_the_issue_wrote_it():
    cfg = harness.load_cell(CELL)["config_data"]
    p = shapes_brumby.params(cfg)
    assert p["layer"] == pytest.approx(330.3e6, rel=1e-3)
    assert p["embedding"] == p["head"] == 151936 * 5120
    assert p["all"] == pytest.approx(4198e6, abs=1e6)
    # one row of one layer: 8 heads x 8256 x 129 float32 = 34.1 MB
    assert shapes_brumby.state_values(cfg) * 4 == pytest.approx(34.08e6,
                                                                rel=1e-3)
    step = shapes_brumby.decode_step(cfg, 16)
    state = shapes_brumby.retention_step(cfg, 16)
    assert state["state_bytes"] == pytest.approx(8.72e9, rel=2e-3)
    assert step["bytes"] == pytest.approx(15.57e9, rel=2e-3)
    assert 0.55 < state["bytes"] / step["bytes"] < 0.57


def test_configuration_keeps_every_number_of_the_published_config():
    """``BENCHMARK.json``'s rule, held here too: every key of the
    catalog's ``config`` is in the file under the same name, and only the
    keys in ``reduced`` differ."""
    cfg = harness.load_cell(CELL)["config_data"]
    published = {"attention_bias": False, "head_dim": 128,
                 "hidden_act": "silu", "hidden_size": 5120,
                 "intermediate_size": 17408, "max_position_embeddings": 32768,
                 "max_window_layers": 40, "model_type": "brumby",
                 "num_attention_heads": 40, "num_hidden_layers": 40,
                 "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
                 "rope_scaling": None, "rope_theta": 1000000,
                 "sliding_window": None, "tie_word_embeddings": False,
                 "use_sliding_window": False, "vocab_size": 151936}
    differ = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differ == sorted(cfg["reduced"]) == ["max_position_embeddings",
                                                "num_hidden_layers"]
    assert {k: published[k] for k in differ} == cfg["published"]
    assert cfg["num_hidden_layers"] == cfg["num_layers"] == 8 \
        == len(cfg["layer_types"])
    for item in ("power", "gate", "normaliser", "scale",
                 "q/k norm and rotation", "float32", "retention_chunk"):
        assert item in cfg["assumed"], item
    assert "stage 1 of 5" in cfg["deployment"]


def test_request_list_is_the_cells_and_keeps_its_separation():
    traffic = harness.load_cell(CELL)["traffic_data"]
    reqs = np.asarray(traffic["requests"])
    assert reqs.shape == (192, 2) and traffic["clients"] == 16 \
        == traffic["max_batch"]
    assert reqs[:, 0].min() >= 257 and reqs[:, 0].max() <= 512
    assert reqs[:, 1].min() >= 256 and reqs[:, 1].max() <= 640
    assert reqs[0, 1] == 256        # the window opens at the first completion
    assert traffic["batcher_kwargs"]["prefix_cache"] is False
    assert "kv_pool_pages" not in traffic["batcher_kwargs"]
    # admission steps: a request of n tokens seated at a ends at a + n - 1
    at = np.zeros(16, int)
    taken = []
    for k in range(12):
        at = at + reqs[k * 16:(k + 1) * 16, 1] - 1
        taken.extend(at.tolist())
    gaps = np.diff(np.sort(taken))
    assert gaps.min() >= 4


def test_the_parent_fails_the_cell_at_once():
    """The adapter raises while it is imported, in the driver process and
    before anything is booted, where the program has no retention layer."""
    src = open(os.path.join(ROOT, "benchmark", "models", "brumby.py")).read()
    head = src.split("gpt2 = harness.load_module")[0]
    assert "power_retention.py" in head and "raise RuntimeError" in head
