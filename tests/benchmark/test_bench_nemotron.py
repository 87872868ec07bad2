"""CPU rehearsal of the ``nemotron-3-nano-batch-decode`` cell's plumbing at
toy size (``toy-nemotron`` / ``toy-nemotron-batch-decode``, files in no
manifest): the adapter boots with K/V pages, SSM state and convolution tail
in one model and its counters move, the four new readers read a stored
reduced trace, ``trace_ssm`` sums a kernel by its operation name and the
``ssm/`` and ``moe/`` scopes, the shape functions count the cut as ISSUE 42
wrote it, the configuration keeps the catalog's numbers, the request list
keeps its separation, and the fp8 control fails the logit comparison."""

import json
import os

import numpy as np
import pytest

from bench_helpers import ROOT, run_toy

from benchmark import harness, shapes_nemotron, trace_ssm

CELL = "nemotron-3-nano-batch-decode"
CONFIG = "nemotron-3-nano-30b-a3b"
NEW_METRICS = ("ssm_moe_decode_step_roofline", "ssm_step_roofline",
               "held_expert_matmul_roofline.serve",
               "ssm_scan_device_ms.serve")
#: the accepted lists the cell joined
JOINED = ("tokens_per_s", "gap_p99_ms", "decode_rows_per_step",
          "prefill_step_share.serve", "ttft_p50_ms.closed",
          "step_period_ms.serve", "device_idle_share.serve")
#: the six whose accepted lists cannot take the cell, under names of the
#: cell's own: ``layer_metrics/<stem>.nemotron.py`` calls the accepted reader
OWN = {f"{stem}.serve": f"{stem}.nemotron" for stem in (
    "decode_device_ms", "prefill_device_ms", "unattributed_gap_share",
    "host_turn_ms", "step_mfu", "expert_peak_load")}


@pytest.mark.integration
def test_toy_nemotron_cell_boots_and_its_counters_move(capfd):
    result = run_toy("toy-nemotron-batch-decode", 2900000117, trace=1,
                     control=True)
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"]["decode_rows_per_step"]["value"] > 0
    out = capfd.readouterr().out
    said = [json.loads(line) for line in out.splitlines()
            if line.startswith('{"fact"')]
    window = next(s for s in said if s["fact"] == "serve window")
    c = window["counters"]
    for name in ("tfos_replica_state_rows_seated_total",
                 "tfos_replica_state_bytes_moved_total",
                 "tfos_replica_expert_assignments_held_total",
                 "tfos_replica_experts_touched_total",
                 "tfos_replica_prefill_experts_touched_total",
                 "tfos_replica_decode_ahead_dispatches_total"):
        assert c[name] > 0, name
    # a prefill's experts touched are a part of all that were
    assert c["tfos_replica_prefill_experts_touched_total"] \
        < c["tfos_replica_experts_touched_total"]
    # 4 of the 16 experts are held: about a quarter of the choices
    share = c["tfos_replica_expert_assignments_held_total"] \
        / c["tfos_replica_expert_assignments_total"]
    assert 0.1 < share < 0.45
    # the program's own account of the state a decode step moved: 4 rows
    # x 2 Mamba-2 layers x (8 x 16 x 8 + 3 x 160) float32 (the toy serves
    # in float32: its configuration's ``assumed`` says why), read
    # and written once
    per_step = c["tfos_replica_state_bytes_moved_total"] \
        / c["tfos_replica_decode_dispatches_total"]
    assert per_step == 2 * 4 * 2 * (8 * 16 * 8 * 4 + 3 * 160 * 4)
    # on the CPU there is no device trace: none of the four new readers
    # finds something to read, and none raises
    assert not set(NEW_METRICS) & set(result["metrics"])
    assert result["metrics"]["host_turn_ms.serve"]["value"] > 0
    # the cell's own names for the six read what the accepted readers do
    for name in ("host_turn_ms", "expert_peak_load"):
        assert result["metrics"][f"{name}.nemotron"] \
            == result["metrics"][f"{name}.serve"]
    # the fp8 control fails the comparison the sound streams pass
    assert any(s["fact"] == "routing" for s in said)
    control = next(s for s in said if s["fact"] == "control")
    limits = harness.load_cell("toy-nemotron-batch-decode")[
        "config_data"]["limits"]
    # (the mean decides, as at full size: the worst of some 65 tokens is
    # one token's luck)
    assert control["served_gap_mean_sigmas"] \
        > 2 * limits["served_gap_mean_sigmas"]
    compared = result["compared"]
    assert compared["served_gap_mean_sigmas"]["value"] \
        < 0.1 * limits["served_gap_mean_sigmas"]


def test_manifest_holds_the_new_entries_to_their_files():
    manifest = harness.manifest()
    config, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["reduced"] == ["n_routed_experts", "vocab_size",
                                 "max_position_embeddings"]
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    on_disk = harness.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) \
        == (CONFIG, "batch-decode-32-chat", 1, on_disk["why"])
    listed = {m["name"]: m for m in manifest["per_layer"]
              + manifest["end_to_end"]}
    # membership and content only: a later PR appends cells, entries and
    # names to these lists without an edit here
    for name in NEW_METRICS + tuple(OWN.values()):
        assert CELL in listed[name]["workloads"]
        assert callable(harness.reader_of(name, on_disk).read)
    assert {listed[n]["moves"] for n in NEW_METRICS[:3]} == {"tokens_per_s"}
    assert listed[NEW_METRICS[3]]["moves"] == "gap_p99_ms"
    assert {n for n, m in listed.items() if CELL in m.get("workloads", ())} \
        >= set(JOINED) | set(NEW_METRICS) | set(OWN.values())
    # the whole step's share has a ``step_work`` for ``step_mfu.serve``
    assert hasattr(harness.load_module("layer_metrics", NEW_METRICS[0]),
                   "step_work")


def _stored_run(cell=CELL):
    """What the runner hands the readers, with a reduced trace as the
    observer stores it: 24 decode runs of 18 ms, 23 kernel calls a run,
    3 prefills."""
    cell = harness.load_cell(cell)
    steps, prefills = 2000.0, 250.0
    e_layers, made = 23, 23 * 6
    return {
        "kind": "serve-closed", "cell": cell,
        "device": {"kind": "TPU v5 lite"}, "mean_context_tokens": 300.0,
        "counters": {
            "tfos_replica_steps_total": steps,
            "tfos_replica_tokens_total": 32 * steps - 40 + prefills,
            "tfos_replica_decode_dispatches_total": steps,
            "tfos_replica_prefill_dispatches_total": prefills,
            "tfos_replica_expert_assignments_total":
                made * (32 * steps + 256 * prefills),
            "tfos_replica_expert_assignments_held_total":
                made * (32 * steps + 256 * prefills) / 8,
            "tfos_replica_expert_peak_assignments_total":
                e_layers * (5 * steps + 40 * prefills),
            "tfos_replica_experts_touched_total":
                e_layers * (12.6 * steps + 15 * prefills),
            "tfos_replica_prefill_experts_touched_total":
                e_layers * 15 * prefills,
            "tfos_replica_state_rows_seated_total": prefills,
            "tfos_replica_state_bytes_moved_total":
                steps * 23 * 32 * 2 * (2_097_152 + 3 * 6144 * 2)},
        "trace": {
            "main_program": "jit_tfos_decode", "steps": 24,
            "programs": {"jit_tfos_decode": {"runs": 24, "seconds": 0.432},
                         "jit_tfos_prefill": {"runs": 3, "seconds": 0.15}},
            "ssm": {"jit_tfos_decode": {
                "runs": 24, "seconds": 0.432,
                "kernels": {"tfos_ssm_step": {"seconds": 0.12, "calls": 552},
                            "tfos_grouped_matmul": {"seconds": 0.2,
                                                    "calls": 1104}},
                "scopes": {"ssm/step": 0.125, "ssm/in_proj": 0.05,
                           "ssm/out_proj": 0.02, "ssm/conv": 0.004,
                           "ssm/gate_norm": 0.003, "moe/experts": 0.2,
                           "moe/shared": 0.03}},
                "jit_tfos_prefill": {
                    "runs": 3, "seconds": 0.15, "kernels": {},
                    "scopes": {"ssm/scan": 0.06, "ssm/in_proj": 0.01,
                               "moe/experts": 0.03}}}}}


def test_the_new_readers_read_a_stored_reduced_trace(capsys):
    run = _stored_run()
    got = {name: harness.load_module("layer_metrics", name).read(run)
           for name in NEW_METRICS}
    rows = 32 - 40 / 2000
    # 23 layers x ~32 rows x 2 x 2.1 MB = 3.09 GB at 819 GB/s = 3.77 ms of
    # the kernels' 5 ms a run (the mean call x 23)
    assert got["ssm_step_roofline"] == pytest.approx(75.6, abs=0.5)
    # 12.6 of 16 held experts x 23 layers + the shared experts: 6.73 GB =
    # 8.2 ms of the scopes' 9.58 ms
    assert got["held_expert_matmul_roofline.serve"] \
        == pytest.approx(85.8, abs=0.7)
    # 12.07 GB = 14.7 ms of an 18 ms step
    assert got["ssm_moe_decode_step_roofline"] == pytest.approx(81.9,
                                                                abs=0.7)
    assert got["ssm_scan_device_ms.serve"] == pytest.approx(20.0)
    assert all(0 < got[n] < 100 for n in NEW_METRICS[:3])
    said = [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]
    roofs = {s["metric"]: s for s in said if s["fact"] == "roofline"}
    assert set(roofs) == set(NEW_METRICS[:3])
    assert all(s["bound"] == "memory" for s in roofs.values())
    step = roofs["ssm_step_roofline"]
    assert step["rows"] == pytest.approx(rows)
    assert step["calls_per_run"] == 23
    # the program moves the whole batch's state and the convolution tails
    assert step["program_state_gb_per_step"] \
        > step["least_state_gb_per_step"] > 3.0
    whole = roofs["ssm_moe_decode_step_roofline"]
    assert whole["held_experts_touched_per_step"] == pytest.approx(23 * 12.6)
    assert whole["held_share_of_assignments"] == pytest.approx(0.125)
    assert whole["mfu"] > 0
    by_scope, = [s for s in said if s["fact"] == "decode device time by scope"]
    assert list(by_scope["ms_per_run"])[0] == "moe/experts"
    # ``step_mfu.serve`` takes this reader's ``step_work`` the day its list
    # takes the cell: the operations over the peak and the run's time
    work, seconds, _ = harness.load_module(
        "layer_metrics", NEW_METRICS[0]).step_work(run)
    assert 100 * work["flops"] / 197e12 / seconds \
        == pytest.approx(whole["mfu"]) and seconds == pytest.approx(0.018)
    # under the cell's own name the same, and the busiest held expert's
    # load over an even share of all 128 experts' choices
    own = {name: harness.load_module("layer_metrics", name).read(run)
           for name in OWN.values()}
    assert own["step_mfu.nemotron"] == pytest.approx(whole["mfu"])
    assert own["decode_device_ms.nemotron"] == pytest.approx(18.0)
    assert own["prefill_device_ms.nemotron"] == pytest.approx(50.0)
    c = run["counters"]
    assert own["expert_peak_load.nemotron"] == pytest.approx(
        c["tfos_replica_expert_peak_assignments_total"] * 128
        / c["tfos_replica_expert_assignments_total"])
    # another model's run reads nothing under these names
    other = dict(run, cell=harness.load_cell("lfm2-8b-a1b-batch-decode"))
    assert {harness.load_module("layer_metrics", name).read(other)
            for name in OWN.values()} == {None}
    # a session that opened inside a run holds only its later calls: the
    # share is of the mean call, and does not rise
    run = _stored_run()
    decode = run["trace"]["ssm"]["jit_tfos_decode"]
    decode["runs"] = 25
    decode["kernels"]["tfos_ssm_step"] = {"seconds": 0.12 * 554 / 552,
                                          "calls": 554}
    assert harness.load_module("layer_metrics", NEW_METRICS[1]).read(run) \
        == pytest.approx(got["ssm_step_roofline"])


@pytest.mark.parametrize("strip", ["counters", "reduction", "trace",
                                   "config", "prefill"])
def test_the_new_readers_return_nothing_where_there_is_nothing_to_read(
        strip):
    """The parent of this PR (no held-experts counter, no ``ssm`` scopes:
    its observer's trace has no ``ssm`` key), an untraced run, another
    model's cell, a session that held no admission: nothing is read and
    nothing is raised."""
    run = _stored_run()
    if strip == "counters":
        run["counters"] = {k: v for k, v in run["counters"].items()
                           if "held" not in k and "state_" not in k}
    elif strip == "reduction":
        del run["trace"]["ssm"]
    elif strip == "trace":
        run["trace"] = None
    elif strip == "config":
        # (whose observer joins no ``ssm`` reduction to the trace)
        run["cell"] = harness.load_cell("lfm2-8b-a1b-batch-decode")
        del run["trace"]["ssm"]
    else:
        del run["trace"]["ssm"]["jit_tfos_prefill"]
    got = {name: harness.load_module("layer_metrics", name).read(run)
           for name in NEW_METRICS}
    if strip in ("trace", "config"):
        assert got == dict.fromkeys(NEW_METRICS)
    elif strip == "counters":
        assert [n for n, v in got.items() if v is not None] \
            == ["ssm_scan_device_ms.serve"]
    elif strip == "reduction":
        assert [n for n, v in got.items() if v is not None] \
            == ["ssm_moe_decode_step_roofline"]
    else:
        assert got["ssm_scan_device_ms.serve"] is None
        assert all(got[n] is not None for n in NEW_METRICS[:3])


def test_trace_ssm_sums_kernels_by_name_and_scopes_by_path():
    """A built trace: two runs of the decode program, in each two kernel
    calls of the state-space step, one grouped product under
    ``moe/experts``, one fusion under ``moe/shared``; one stray operation
    outside any run."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].name = "tf_op"
    names = {1: ("jit_tfos_decode(123)", ""),
             2: ("%tfos_ssm_step.3 = f32[32,8,128,512] custom-call()",
                 "jit(tfos_decode)/GPT/layer_0/ssm/step/pallas_call"),
             3: ("%tfos_grouped_matmul.7 = bf16[256,1856] custom-call()",
                 "jit(tfos_decode)/GPT/layer_1/moe/experts/pallas_call"),
             4: ("%fusion.9 = f32[32,3712] fusion()",
                 "jit(tfos_decode)/GPT/layer_1/moe/shared/dot_general"),
             5: ("%fusion.11 = f32[32] fusion()",
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
                           duration_ps=60_000_000)
        for k, (mid, dur) in enumerate(((2, 10_000_000), (3, 7_000_000),
                                        (2, 11_000_000), (4, 2_000_000),
                                        (5, 1_000_000))):
            ops.events.add(metadata_id=mid, offset_ps=start + k * 12_000_000,
                           duration_ps=dur)
    ops.events.add(metadata_id=2, offset_ps=80_000_000, duration_ps=5_000_000)
    got = trace_ssm.reduce_space(space)["jit_tfos_decode"]
    assert got["runs"] == 2
    assert got["kernels"]["tfos_ssm_step"] == {
        "seconds": pytest.approx(42e-6), "calls": 4}
    assert got["kernels"]["tfos_grouped_matmul"]["calls"] == 2
    assert got["scopes"] == {"ssm/step": pytest.approx(42e-6),
                             "moe/experts": pytest.approx(14e-6),
                             "moe/shared": pytest.approx(4e-6)}
    # a trace with neither a kernel nor a scope of the list reads nothing
    for e in list(ops.events):
        e.metadata_id = 5
    assert trace_ssm.reduce_space(space) is None


def test_shapes_count_the_cut_as_the_issue_wrote_it():
    cfg = harness.load_cell(CELL)["config_data"]
    p = shapes_nemotron.params(cfg)
    assert p["layers"] == {"mamba2": 23, "experts": 23, "attention": 6}
    assert p["mamba2"] == pytest.approx(38.74e6, rel=1e-3)
    assert p["attention"] == pytest.approx(23.40e6, rel=1e-3)
    assert p["expert"] == pytest.approx(9.978e6, rel=1e-3)
    assert p["shared"] == pytest.approx(19.96e6, rel=1e-3)
    assert p["all"] == pytest.approx(5258e6, abs=1e6)
    whole = dict(cfg, **cfg["published"])
    assert shapes_nemotron.params(whole)["all"] == pytest.approx(31.58e9,
                                                                 rel=1e-3)
    # one row of one layer: 64 x 64 x 128 float32 = 2.10 MB
    assert shapes_nemotron.state_values(cfg) * 4 == 2_097_152
    state = shapes_nemotron.ssm_step(cfg, 32)
    assert state["state_bytes"] == pytest.approx(3.09e9, rel=2e-3)
    # at 32 rows a step makes 192 assignments over 128 experts and touches
    # 1 - (122/128)^32 = 0.785 of the 16 held: ~12.6 an expert layer
    touched = 23 * 16 * (1 - (122 / 128) ** 32)
    experts = shapes_nemotron.expert_matmuls(cfg, 32, touched)
    assert experts["bytes"] == pytest.approx(6.7e9, rel=0.01)
    step = shapes_nemotron.decode_step(cfg, 32, 32 * 300, touched)
    assert step["bytes"] == pytest.approx(12.05e9, rel=0.01)
    assert 0.79 < (state["bytes"] + experts["bytes"]) / step["bytes"] < 0.83
    assert step["flops"] / 197e12 < step["bytes"] / 819e9     # memory-bound


def test_configuration_keeps_every_number_of_the_published_config():
    """``BENCHMARK.json``'s rule, held here too: every key of the
    catalog's ``config`` is in the file under the same name, and only the
    keys in ``reduced`` differ; no width among them."""
    cfg = harness.load_cell(CELL)["config_data"]
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 52, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    differ = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differ == sorted(cfg["reduced"]) == [
        "max_position_embeddings", "n_routed_experts", "vocab_size"]
    assert {k: published[k] for k in differ} == cfg["published"]
    assert (cfg["n_routed_experts"], cfg["num_experts"],
            cfg["experts_held_first"], cfg["vocab_size"]) \
        == (16, 128, 0, 16384)
    pattern = cfg["hybrid_override_pattern"]
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (52, 23, 23, 6) \
        and cfg["num_layers"] == 52
    for item in ("position", "block", "mamba2", "gated norm", "experts",
                 "experts held", "weights", "dtype", "control_precision"):
        assert item in cfg["assumed"], item
    assert "chip 0 of the 8" in cfg["deployment"] \
        and "5,258 M parameters" in cfg["deployment"]
    assert "limits" not in cfg
    # the adapter hands the program the published widths
    g = harness.load_module("models", cfg["model"]).gpt_config(cfg)
    assert (g.hidden_size, g.head_dim, g.num_heads, g.num_kv_heads) \
        == (2688, 128, 32, 2)
    assert (g.ssm_inner, g.ssm_conv_channels, g.ssm_groups,
            g.ssm_state_size, g.ssm_conv_kernel) == (4096, 6144, 8, 128, 4)
    assert (g.num_experts, g.experts_held, g.num_experts_per_tok,
            g.moe_intermediate_size, g.moe_shared_intermediate_size,
            g.routed_scaling_factor) == (128, (0, 16), 6, 1856, 3712, 2.5)
    assert (g.num_expert_layers, g.num_attention_layers, g.num_state_layers,
            g.pos_encoding, g.mixer_only) == (23, 6, 23, "none", True)


def test_request_list_is_the_cells_and_keeps_its_separation():
    traffic = harness.load_cell(CELL)["traffic_data"]
    reqs = np.asarray(traffic["requests"])
    assert reqs.shape == (384, 2) and traffic["clients"] == 32 \
        == traffic["max_batch"]
    assert reqs[:, 0].min() >= 129 and reqs[:, 0].max() <= 256
    assert reqs[:, 1].min() >= 128 and reqs[:, 1].max() <= 320
    assert reqs[0, 1] == 128        # the window opens at the first completion
    assert reqs[:, 1].mean() == pytest.approx(228.0, abs=0.05)
    kwargs = traffic["batcher_kwargs"]
    assert kwargs["prefix_cache"] is False and kwargs["kv_page_tokens"] == 16
    # the pool holds every row at its longest: 32 x (256 + 320) tokens
    assert kwargs["kv_pool_pages"] * 16 >= 32 * (256 + 320)
    at = np.zeros(32, int)
    taken = []
    for k in range(12):
        at = at + reqs[k * 32:(k + 1) * 32, 1] - 1
        taken.extend(at.tolist())
    assert np.diff(np.sort(taken)).min() >= 4


def test_the_parent_fails_the_cell_at_once():
    """The adapter raises while it is imported, in the driver process and
    before anything is booted, where the program has no state-space
    layer."""
    src = open(os.path.join(ROOT, "benchmark", "models",
                            "nemotron_h.py")).read()
    head = src.split("gpt2 = harness.load_module")[0]
    assert "ops\", \"ssm.py" in head and "raise RuntimeError" in head
