"""CPU rehearsal of the ``lfm2-8b-a1b-batch-decode`` cell's plumbing at toy
size (``toy-lfm2`` / ``toy-lfm2-batch-decode``, files in no manifest): the
adapter boots and its counters move, the new readers read a stored reduced
trace, the shape functions count the cut, the request list keeps its
separation, and the fp8 control fails the logit comparison."""

import json

import numpy as np
import pytest

from bench_helpers import run_toy

from benchmark import harness, shapes_lfm2, trace_scopes

CELL = "lfm2-8b-a1b-batch-decode"
NEW_METRICS = ("moe_decode_step_roofline", "expert_matmul_roofline.serve",
               "expert_peak_load.serve", "conv_device_ms.serve")


@pytest.mark.integration
def test_toy_lfm2_cell_boots_and_its_counters_move(capfd):
    result = run_toy("toy-lfm2-batch-decode", 3000000328, trace=1)
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"]["decode_rows_per_step"]["value"] > 0
    # the four readers are in the manifest for this cell alone (PR 38);
    # on the CPU there is no device trace, so only the counters' reader
    # finds something to read
    listed = {m["name"]: m for m in harness.manifest()["per_layer"]}
    for name in NEW_METRICS:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "tokens_per_s"
        assert callable(harness.load_module("layer_metrics", name).read)
    assert result["metrics"]["expert_peak_load.serve"]["value"] >= 1.0
    assert result["metrics"]["host_turn_ms.serve"]["value"] > 0
    assert not {"moe_decode_step_roofline", "expert_matmul_roofline.serve",
                "conv_device_ms.serve", "step_mfu.serve"} \
        & set(result["metrics"])
    out = capfd.readouterr().out
    window = next(json.loads(line) for line in out.splitlines()
                  if line.startswith('{"fact": "serve window"'))
    peak = harness.load_module("layer_metrics", "expert_peak_load.serve")
    assert peak.read({"counters": window["counters"], "cell":
                      harness.load_cell("toy-lfm2-batch-decode")}) >= 1.0
    for name in ("tfos_replica_expert_assignments_total",
                 "tfos_replica_expert_peak_assignments_total",
                 "tfos_replica_experts_touched_total",
                 "tfos_replica_state_rows_seated_total"):
        assert window["counters"][name] > 0, name
    assert '"fact": "routing"' in out


def _stored_run(cell=CELL):
    """What the runner hands the readers, with a reduced trace as the
    observer stores it: 24 decode runs of 20 ms, 3 prefills."""
    cell = harness.load_cell(cell)
    steps, prefills = 1000.0, 120.0
    layers = 14
    return {
        "kind": "serve-closed", "cell": cell,
        "device": {"kind": "TPU v5 lite"}, "mean_context_tokens": 900.0,
        "counters": {
            "tfos_replica_steps_total": steps,
            "tfos_replica_tokens_total": 32 * steps,
            "tfos_replica_decode_dispatches_total": steps,
            "tfos_replica_prefill_dispatches_total": prefills,
            "tfos_replica_expert_assignments_total":
                layers * 4 * (32 * steps + 1024 * prefills),
            "tfos_replica_expert_peak_assignments_total":
                layers * (9 * steps + 160 * prefills),
            "tfos_replica_experts_touched_total":
                layers * (31 * steps + 32 * prefills),
            "tfos_replica_state_rows_seated_total": prefills},
        "trace": {
            "main_program": "jit_tfos_decode", "steps": 24,
            "programs": {"jit_tfos_decode": {"runs": 24, "seconds": 0.48},
                         "jit_tfos_prefill": {"runs": 3, "seconds": 0.6}},
            "scopes": {"jit_tfos_decode": {
                "runs": 24, "seconds": 0.48,
                "scopes": {"moe/experts": 0.36, "moe/router": 0.005,
                           "conv/in_proj": 0.012, "conv/mix": 0.002,
                           "conv/state_store": 0.001,
                           "conv/out_proj": 0.005,
                           "attn/kv_gather": 0.03}}}}}


def test_the_new_readers_read_a_stored_reduced_trace(capsys):
    run = _stored_run()
    got = {name: harness.load_module("layer_metrics", name).read(run)
           for name in NEW_METRICS}
    # 10.7 GB at 819 GB/s = 13.1 ms of a 20 ms step
    assert got["moe_decode_step_roofline"] == pytest.approx(65.6, abs=1.0)
    # 14 x 31 experts x 22 MB = 9.56 GB: 11.7 ms of 15 ms under moe/experts
    assert got["expert_matmul_roofline.serve"] == pytest.approx(78.0, abs=1.5)
    assert got["conv_device_ms.serve"] == pytest.approx(1e3 * 0.02 / 24)
    a = 4 * (32 * 1000 + 1024 * 120)
    assert got["expert_peak_load.serve"] == pytest.approx(
        (9 * 1000 + 160 * 120) * 32 / a)
    said = [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]
    by_scope = [s for s in said if s["fact"] == "decode device time by scope"]
    assert by_scope[0]["ms_per_run"]["moe/experts"] == pytest.approx(15.0)
    assert list(by_scope[0]["ms_per_run"])[:2] == ["moe/experts",
                                                   "attn/kv_gather"]
    said = [s for s in said if s["fact"] == "roofline"]
    assert {s["metric"] for s in said} == set(NEW_METRICS[:2])
    assert all(s["bound"] == "memory" and
               s["experts_touched_per_step"] == pytest.approx(14 * 31)
               for s in said)


def test_a_cell_reports_only_the_metrics_that_list_it():
    """The dense model's roofline reader finds numbers in this cell's run
    too (24.5 % of a program that never ran; my chip runs, PR 28); the
    manifest does not list the cell for it, and the harness no longer
    calls it there.  A cell in no manifest still gets every reader."""
    run = _stored_run()
    assert harness.load_module(
        "layer_metrics", "decode_step_roofline").read(run) is not None
    got = harness.layer_metrics(dict(run, window_s=45.0, warmup_s=30.0,
                                     ttft_ms=[70.0], idle=None))
    assert "decode_step_roofline" not in got
    assert {"moe_decode_step_roofline", "step_mfu.serve",
            "decode_device_ms.serve"} <= set(got)
    toy = dict(run, cell=dict(run["cell"], name="toy-lfm2-batch-decode"))
    assert "decode_step_roofline" in harness.layer_metrics(
        dict(toy, window_s=45.0, warmup_s=30.0, ttft_ms=[70.0], idle=None))
    names = [m["name"] for m in harness.declared_for("end_to_end", CELL)]
    assert names == ["tokens_per_s", "gap_p99_ms", "setup_s"]


@pytest.mark.parametrize("strip", ["counters", "scopes", "trace", "config"])
def test_the_new_readers_return_nothing_where_there_is_nothing_to_read(
        strip):
    """A program from before the counters and scopes (the parent of the PR
    that added them), an untraced run, a configuration without experts:
    nothing is read and nothing is raised."""
    run = _stored_run()
    if strip == "counters":
        run["counters"] = {k: v for k, v in run["counters"].items()
                           if "expert" not in k and "state_rows" not in k}
    elif strip == "scopes":
        del run["trace"]["scopes"]
    elif strip == "trace":
        run["trace"] = None
    else:
        run = dict(_stored_run("gpt2xl-batch-decode"),
                   counters=run["counters"])
    got = {name: harness.load_module("layer_metrics", name).read(run)
           for name in NEW_METRICS}
    want_none = {"counters": NEW_METRICS[:3], "scopes": NEW_METRICS[1:2]
                 + NEW_METRICS[3:], "trace": NEW_METRICS[:2]
                 + NEW_METRICS[3:], "config": NEW_METRICS[:3]}[strip]
    for name in want_none:
        assert got[name] is None, name


def test_scopes_are_summed_by_program_from_the_ops_metadata():
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].name = "tf_op"
    paths = {10: "jit(tfos_decode)/GPT/layer_3/moe/experts/ragged_dot:",
             11: "jit(tfos_decode)/GPT/layer_3/conv/mix/mul:",
             12: "jit(tfos_decode)/GPT/layer_2/attn/out/dot_general:",
             13: "jit(tfos_prefill)/GPT/layer_3/moe/experts/ragged_dot:",
             14: "ragged-dot-none"}      # the compiler's own call: no path
    for mid, path in paths.items():
        md = plane.event_metadata[mid]
        md.name = f"%fusion.{mid} = f32[4]" if mid != 14 \
            else "%ragged-dot-none.40 = f32[128,1792]{1,0} custom-call("
        stat = md.stats.add(metadata_id=1)
        stat.str_value = path
    for mid, name in ((20, "jit_tfos_decode(123)"),
                      (21, "jit_tfos_prefill(456)")):
        plane.event_metadata[mid].name = name
    modules = plane.lines.add(name="XLA Modules")
    ops = plane.lines.add(name="XLA Ops")
    for i in range(3):
        modules.events.add(metadata_id=20, offset_ps=i * 10_000_000,
                           duration_ps=8_000_000)
        for mid, dur in ((10, 5_000_000), (11, 1_000_000), (12, 2_000_000),
                         (14, 500_000)):
            ops.events.add(metadata_id=mid, offset_ps=i * 10_000_000,
                           duration_ps=dur)
    modules.events.add(metadata_id=21, offset_ps=40_000_000,
                       duration_ps=9_000_000)
    ops.events.add(metadata_id=13, offset_ps=40_000_000,
                   duration_ps=7_000_000)
    got = trace_scopes.reduce_space(space)
    assert got["jit_tfos_decode"]["runs"] == 3
    assert got["jit_tfos_decode"]["scopes"] == pytest.approx(
        {"moe/experts": 16.5e-6, "conv/mix": 3e-6})
    assert got["jit_tfos_prefill"]["scopes"] == pytest.approx(
        {"moe/experts": 7e-6})
    # a trace whose operations carry no such metadata and no such name
    # reduces to nothing
    for md in plane.event_metadata.values():
        del md.stats[:]
    plane.event_metadata[14].name = "%fusion.14 = f32[4]"
    assert trace_scopes.reduce_space(space) is None


def test_shape_functions_count_the_cut():
    cfg = harness.load_json("configs", "lfm2-8b-a1b.json")
    p = shapes_lfm2.params(cfg)
    assert p["all"] == pytest.approx(5_399e6, abs=1e6)
    assert (p["expert_layers"], p["conv_layers"], p["attention_layers"]) \
        == (14, 12, 4)
    assert p["expert"] * 32 == pytest.approx(352.3e6, rel=1e-3)
    step = shapes_lfm2.decode_step(cfg, 32, 32 * 900, 14 * 31)
    # 8 KB of K/V a token; the touched experts are nine tenths of the bytes
    experts = shapes_lfm2.expert_matmuls(cfg, 32, 14 * 31)
    assert 0.85 < experts["bytes"] / step["bytes"] < 0.92
    assert step["bytes"] == pytest.approx(10.76e9, rel=0.01)
    assert step["bytes"] / 819e9 > step["flops"] / 197e12    # memory bound
    # the manifest's entry says what the file says
    entry = next(c for c in harness.manifest()["configs"]
                 if c["name"] == "lfm2-8b-a1b")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "layer_types", "max_position_embeddings"]
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:16]
    assert CELL not in next(
        m for m in harness.manifest()["per_layer"]
        if m["name"] == "decode_step_roofline")["workloads"]


def test_the_list_keeps_its_separation_and_its_one_bucket():
    traffic = harness.load_json("traffic", "batch-decode-32.json")
    reqs, clients = traffic["requests"], traffic["clients"]
    assert (clients, traffic["max_batch"], len(reqs)) == (32, 32, 384)
    assert all(513 <= p <= 1024 and 128 <= n <= 320 for p, n in reqs)
    assert reqs[0][1] == 128          # the window opens at its completion
    # a request of n tokens admitted at step a ends at a + n - 1, where the
    # caller's next is admitted (tools/make_request_list.py)
    admissions = []
    for i in range(clients):
        at = 0
        for _, n in reqs[i::clients]:
            at += n - 1
            admissions.append(at)
    gaps = np.diff(np.sort(admissions))
    assert gaps.min() >= 4
    # every row's window fits the pool and the served window
    cfg = harness.load_json("configs", "lfm2-8b-a1b.json")
    kw = traffic["batcher_kwargs"]
    assert max(p + n for p, n in reqs) <= cfg["max_position_embeddings"]
    pages = sum(sorted((-(-(p + n) // kw["kv_page_tokens"])
                        for p, n in reqs), reverse=True)[:clients])
    assert pages <= kw["kv_pool_pages"] and kw["prefix_cache"] is False
    assert max(traffic["warm_groups"]) == kw["prefill_rows_max"]
    # nothing the batcher does not read: run-ahead is its rule since PR 31
    assert set(kw) == {"kv_page_tokens", "kv_pool_pages", "prefix_cache",
                       "prefill_rows_max"}
    tool = harness.load_module("tools", "make_request_list")
    assert tool.request_list(32, 12, (513, 1024), (128, 320), 4, 24)[
        "requests"] == reqs


def test_fp8_control_fails_the_served_logit_comparison_with_experts():
    """Greedy streams of the reference's own at toy size: they score 0;
    fp8 puts a token first that is far below the reference's best, by the
    toy configuration's limits; and the share of routing decisions that
    bfloat16 rounding changes is said."""
    import jax
    import jax.numpy as jnp

    ref = harness.load_module("reference", "lfm2")
    cfg = dict(harness.load_json("configs", "toy-lfm2.json"),
               dtype="float32")
    limits = {k: v for k, (v, _) in harness.limits_for(ref.LIMITS,
                                                       cfg).items()}
    params = ref.make_weights(44, cfg)
    forward = jax.jit(lambda p, ids: ref.forward(p, ids, cfg))
    rng = np.random.default_rng(44)
    rows, prompt, total = 4, 12, 40
    ids = np.zeros((rows, total), np.int32)
    ids[:, :prompt] = rng.integers(0, cfg["vocab_size"], (rows, prompt))
    with jax.default_matmul_precision("highest"):
        for t in range(prompt, total):
            logits = forward(params, jnp.asarray(ids))
            ids[:, t] = np.asarray(logits[:, t - 1].argmax(-1))
    items = [(ids[r, :prompt], ids[r, prompt:]) for r in range(rows)]
    scored = ref.score(cfg, 44, items, control="fp8")
    assert scored["served_gap_sigmas"] <= 1e-3         # its own argmax
    assert scored["tokens"] == rows * (total - prompt)
    assert 0.0 <= scored["routing_differs_share"] <= 1.0
    # the control fails by at least one of the limits (the mean decides,
    # as at full size)
    assert scored["control"]["served_gap_mean_sigmas"] \
        > limits["served_gap_mean_sigmas"] \
        or scored["control"]["served_gap_sigmas"] \
        > limits["served_gap_sigmas"]
