"""The yardstick itself, in-process and fast: the manifest, the data files
it names, the gap reducer, the fixed request list, the trace reduction,
the idle-share cross-check, the peaks, the shape functions, and the
lower-precision controls that must come out as not correct."""

import json
import math
import os
import re

import numpy as np
import pytest

from bench_helpers import ROOT

from benchmark import harness, shapes, traffic_gen
from benchmark import trace as trace_mod

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.manifest()


# ------------------------------------------------------------- the manifest

def test_manifest_has_exactly_the_contract_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert 40 <= manifest["run_seconds"] <= 51
    full = (2 + 14 * 24) * (manifest["run_seconds"] + 60) + 24 * 180 + 1200
    assert full <= 43200                       # a full check of 24 cells
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_every_name_and_unit_uses_only_the_allowed_characters(manifest):
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[section]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((section in ("end_to_end", "per_layer"),
                          entry["name"]))
    assert len(set(names)) == len(names)
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])


def test_every_entry_finds_its_files_and_every_arrow_its_metric(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    for c in manifest["configs"]:
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        data = json.load(open(os.path.join(ROOT, c["file"])))
        assert data["reduced"] == c["reduced"] and "assumed" in data
        assert any(w["config"] == c["name"] for w in manifest["workloads"])
    for w in manifest["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        kind = cell["traffic_data"]["kind"].replace("-", "_")
        assert hasattr(harness.load_module("runners", kind), "run")
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        moved = end_to_end[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert callable(harness.reader_of(
                m["name"], harness.load_cell(cell)).read), (m["name"], cell)
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    share = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert share <= max(1, len(manifest["workloads"]) // 4)


# ------------------------------------------------------------ gaps and list

def test_the_percentile_is_nearest_rank_never_interpolated():
    values = [251.0] * 94 + [385.0] * 6
    assert harness.nearest_rank(values, 95) == 385.0
    assert harness.nearest_rank(values, 94) == 251.0
    assert harness.nearest_rank([1.0, 2.0, 3.0, 4.0], 50) == 2.0
    assert harness.nearest_rank([7.0], 95) == 7.0


def test_gaps_leave_out_first_tokens_and_tokens_outside_the_window():
    requests = [{"recv": [10.0, 10.25, 10.5, 11.0]},   # first at 10.0
                {"recv": [10.9, 11.4]},                 # second token late
                {"recv": [9.0, 9.9, 10.1]}]             # began before
    gaps = harness.token_gaps(requests, 10.0, 11.2)
    assert sorted(round(g, 3) for g in gaps) == [0.2, 0.25, 0.25, 0.5]
    modes = harness.gap_modes([0.25] * 90 + [0.255] * 4 + [0.385] * 6)
    assert [round(m["share"], 2) for m in modes] == [0.94, 0.06]


def test_a_serve_windows_edge_falls_between_two_steps():
    """Tokens reach the callers a step at a time; an edge of the window is
    laid half a step after the first tokens at or past the asked time, so
    that the window holds whole steps whatever the phase."""
    import threading
    import time

    serve = harness.load_module("runners", "serve_closed")
    arrivals: list[float] = []
    stop = threading.Event()

    def steps():
        while not stop.is_set():
            now = time.monotonic()
            arrivals.extend([now, now + 1e-4, now + 2e-4])   # one step
            time.sleep(0.05)

    thread = threading.Thread(target=steps, daemon=True)
    thread.start()
    try:
        for phase in (0.0, 0.013, 0.031, 0.047):
            after = time.monotonic() + 0.1 + phase
            serve._between_steps(arrivals, after, 0.025)
            edge = time.monotonic()
            first = min(t for t in arrivals if t >= after)
            assert first - after < 0.07
            assert 0.02 <= edge - first <= 0.045, (phase, edge - first)
    finally:
        stop.set()
        thread.join()


def test_the_request_list_is_the_cells_own_and_not_the_seeds():
    cell = harness.load_cell("gpt2xl-batch-decode")
    assert cell["traffic"] == "batch-decode-16-xlong-out"
    traffic, cfg = cell["traffic_data"], cell["config_data"]
    clients = traffic["clients"]
    assert clients == traffic["max_batch"] == 16
    # 14 a caller: a window completes about 130 requests, 8 a caller, so
    # none repeats
    assert len(traffic["requests"]) == 224
    entries = [traffic_gen.client_entries(traffic, i) for i in range(clients)]
    assert sum(len(e) for e in entries) == len(traffic["requests"])
    assert entries[3][1] == tuple(traffic["requests"][3 + clients])
    for prompt, output in traffic["requests"]:
        assert 129 <= prompt <= 256 and 512 <= output <= 750
        assert 1 << (prompt - 1).bit_length() == 256      # one bucket
        assert prompt + output <= cfg["max_position_embeddings"] == 1024
    # nothing is asked of the batcher but its page size: the default pool
    # (every slot a whole window) is what seats these rows, and no dead key
    assert traffic["batcher_kwargs"] == {"kv_page_tokens": 16}
    assert traffic["warm_groups"] == [1, 2, 4, 8, 16]
    assert traffic["open_after_completions"] == clients
    assert traffic["trace_steps"] == 96
    # the tool at its defaults writes this list, to the pair
    tool = harness.load_module("tools", "make_request_list")
    assert tool.request_list(16, 14, (129, 256), (512, 750), 4, 39)[
        "requests"] == traffic["requests"]
    # where the lengths come from, and which cell shows the admissions
    assert "interactive_conditional_samples.py" in traffic["drawn_from"]
    assert "lfm2-8b-a1b-batch-decode" in traffic["drawn_from"]
    # the seed makes the token ids, never the lengths
    a = traffic_gen.prompt_ids(1, 2, 0, 140, 50257)
    b = traffic_gen.prompt_ids(3000000301, 2, 0, 140, 50257)
    assert a.shape == b.shape == (140,) and (a != b).any()
    assert (a == traffic_gen.prompt_ids(1, 2, 0, 140, 50257)).all()
    # no two admissions within 4 serving steps of each other
    steps = np.zeros(clients, int)
    admitted = []
    for k in range(len(entries[0])):
        steps = steps + [entries[i][k][1] - 1 for i in range(clients)]
        admitted += list(steps)
    admitted.sort()
    assert min(b - a for a, b in zip(admitted, admitted[1:])) >= 4


def test_train_pool_rows_all_differ_and_follow_the_seed():
    traffic = harness.load_json("traffic", "toy-fed.json")
    cfg = harness.load_json("configs", "toy-resnet.json")
    rows = traffic_gen.train_pool(2 ** 32 + 7, traffic, cfg, chips=1)
    assert len(rows) == traffic["pool_batches"] * traffic["batch_per_chip"]
    assert len({r[0].tobytes() for r in rows}) == len(rows)
    again = traffic_gen.train_batch(2 ** 32 + 7, 1, traffic, cfg, 1)
    assert (again[0][0] == rows[traffic["batch_per_chip"]][0]).all()
    other = traffic_gen.train_batch(7, 1, traffic, cfg, 1)
    assert (other[0] != again[0]).any()


# ---------------------------------------------------------- trace reduction

def _synthetic(step_s=0.1, steps=12, fetch_every=5, fetch_gap_s=0.05):
    """Runs of one program back to back, an idle gap (the host fetching
    losses) after every ``fetch_every`` runs."""
    modules, ops, host, t = [], [], [], 1.0
    for i in range(steps):
        modules.append(("jit_step(123)", t, step_s))
        ops.append(("%fusion.1 = bf16[8,8]{1,0} fusion(...)", t, step_s / 2))
        ops.append(("%copy.2 = f32[4]{0} copy(...)", t + step_s / 2,
                    step_s / 2))
        t += step_s
        if (i + 1) % fetch_every == 0:
            host.append(("bench/loss_fetch", t, fetch_gap_s))
            t += fetch_gap_s
    return [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": modules},
                {"name": "XLA Ops", "events": ops}]},
            {"name": "/host:CPU", "lines": [
                {"name": "python", "events": host}]}]


def test_reduction_counts_whole_fetch_periods_and_names_the_gaps():
    reduced = trace_mod.reduce(_synthetic(), period=5)
    assert reduced["steps"] == 10 and reduced["main_program"] == "jit_step"
    assert reduced["window_s"] == pytest.approx(10 * 0.1 + 2 * 0.05)
    assert reduced["busy_s"] == pytest.approx(1.0)
    assert reduced["programs"]["jit_step"]["runs"] == 10
    assert dict(map(tuple, reduced["idle_gaps"])) == {
        "bench/loss_fetch": pytest.approx(0.1)}
    per_step = dict(map(tuple, reduced["device_ops"]))
    assert per_step["jit_step/fusion.1 bf16[8,8]"] == pytest.approx(0.05)
    assert per_step["jit_step/copy.2 f32[4]"] == pytest.approx(0.05)
    assert trace_mod.reduce(_synthetic(steps=4), period=5) is None
    assert trace_mod.reduce([{"name": "/host:CPU", "lines": []}]) is None


def _idle(reduced, steps, window_s, capsys):
    device = harness.device_seconds(
        reduced, {(reduced["main_program"],): steps}, steps)
    assert device["missing"] == []
    idle = harness.idle_share(reduced, device["seconds"], window_s)
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["fact"] == "idle share cross-check"
    assert said["differ"] is idle["differ"] is said["session_set_aside"]
    return idle, said


def test_idle_cross_check_trips_on_a_window_between_two_fetches(capsys):
    """PR 23's fault: a traced window that sits between two fetches reads
    0 % idle while the run's own window lost 7 % to them.  The run then
    reports the window's figures, not the session's."""
    reduced = trace_mod.reduce(_synthetic(steps=4, fetch_every=100))
    idle, said = _idle(reduced, 100, 100 * 0.1 * 1.07, capsys)
    assert idle["differ"] is True and said["reported"] == "measured window"
    assert idle["from_trace"] == pytest.approx(0.0, abs=1e-6)
    assert idle["value"] == idle["from_window"] == \
        pytest.approx(100 * (1 - 1 / 1.07))
    assert idle["busy_s"] == pytest.approx(10.0)
    assert idle["window_s"] == pytest.approx(10.7)
    reader = harness.load_module("layer_metrics", "device_idle_share.train")
    run = {"kind": "train-fed", "idle": idle}
    assert reader.read(run) == idle["value"]
    assert reader.read({"kind": "train-fed", "idle": None}) is None
    assert harness.load_module(
        "layer_metrics", "device_idle_share.serve").read(run) is None
    # within 5 points the session's own figures stand
    idle, said = _idle(reduced, 100, 100 * 0.1 * 1.01, capsys)
    assert idle["differ"] is False and said["reported"] == "trace session"
    assert idle["value"] == idle["from_trace"]
    assert (idle["busy_s"], idle["window_s"]) == \
        (reduced["busy_s"], reduced["window_s"])


def _serving(turns, prefill_every, decode_s=0.0155, prefill_s=0.0313,
             host_s=0.0003):
    """A serving loop's device line: a decode run a turn, a prefill run
    before every ``prefill_every``-th, the host's ``host_s`` between."""
    modules, ops, t = [], [], 1.0
    for i in range(turns):
        if i % prefill_every == prefill_every - 1:
            modules.append(("jit_tfos_prefill(9)", t, prefill_s))
            ops.append(("%fusion.7 = bf16[4]{0} fusion(...)", t, prefill_s))
            t += prefill_s + host_s
        modules.append(("jit_tfos_decode(5)", t, decode_s))
        ops.append(("%fusion.3 = bf16[4]{0} fusion(...)", t, decode_s))
        t += decode_s + host_s
    return [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops}]}]


def test_the_windows_device_seconds_count_each_program_by_its_own_runs(
        capsys):
    """A session of 23 turns that holds 4 prefills where the window's share
    would give 3 read -2.96 % idle while the decode program's time x steps
    stood for the window (my chip runs, PR 37): each program's mean run x
    its dispatches in the window agrees with the session."""
    reduced = trace_mod.reduce(_serving(24, 6))
    assert reduced["main_program"] == "jit_tfos_decode"
    assert reduced["programs"]["jit_tfos_prefill"]["runs"] == 4
    decodes, prefills = 2209, 301
    window_s = decodes * 0.0158 + prefills * 0.0316
    device = harness.device_seconds(reduced, {
        trace_mod.DECODE_PROGRAMS: decodes,
        trace_mod.PREFILL_PROGRAMS: prefills}, decodes)
    assert device == {"seconds": pytest.approx(
        decodes * 0.0155 + prefills * 0.0313), "missing": []}
    idle = harness.idle_share(reduced, device["seconds"], window_s)
    assert idle["differ"] is False and 0.0 < idle["from_window"] < 3.0
    # the old figure: all of the session's device time a step x the steps
    old = sum(p["seconds"] for p in reduced["programs"].values()) \
        / reduced["steps"] * decodes
    assert old > window_s
    # a session that held no admission names what it could not count
    quiet = trace_mod.reduce(_serving(24, 100))
    device = harness.device_seconds(quiet, {
        trace_mod.DECODE_PROGRAMS: decodes,
        trace_mod.PREFILL_PROGRAMS: prefills}, decodes)
    assert device["missing"] == ["jit_tfos_prefill"]
    assert device["seconds"] == pytest.approx(decodes * 0.0155)
    # a small program beside the two counts by the loop's steps
    reduced["programs"]["jit_tfos_kv_park"] = {"runs": 3, "seconds": 0.0003}
    more = harness.device_seconds(reduced, {
        trace_mod.DECODE_PROGRAMS: decodes,
        trace_mod.PREFILL_PROGRAMS: prefills}, decodes)
    assert more["seconds"] - (decodes * 0.0155 + prefills * 0.0313) \
        == pytest.approx(0.0003 / reduced["steps"] * decodes)
    capsys.readouterr()


def test_the_main_program_is_chosen_by_name_not_by_device_time():
    """A 121 ms prefill every fourth turn holds the device longer than
    the 26 ms decode step; the window is still cut at the decode step's
    runs.  A trace without any of the names keeps the old rule."""
    reduced = trace_mod.reduce(_serving(12, 4, decode_s=0.026,
                                        prefill_s=0.121))
    programs = reduced["programs"]
    assert programs["jit_tfos_prefill"]["seconds"] \
        > programs["jit_tfos_decode"]["seconds"]
    assert reduced["main_program"] == "jit_tfos_decode"
    assert reduced["steps"] == 11
    assert trace_mod.reduce(_synthetic())["main_program"] == "jit_step"
    train = _synthetic()
    for line in train[0]["lines"]:
        if line["name"] == "XLA Modules":
            line["events"] = [("jit_tfos_train_step(1)", s, d / 4)
                              for _, s, d in line["events"]] \
                + [("jit_other(2)", s + d / 4, d / 2)
                   for _, s, d in line["events"]]
    assert trace_mod.reduce(train)["main_program"] == "jit_tfos_train_step"


def test_the_tail_says_whether_it_lies_inside_a_mode():
    # 3.5 % of the gaps are the admission's turn: the 99th percentile and
    # its neighbours half a point either way lie within 5 % of each other
    gaps = [0.0125] * 9650 + [0.0262 + 1e-6 * i for i in range(350)]
    tail = harness.tail_in_mode(gaps, 99)
    assert tail["inside_a_mode"] is True and tail["beyond"] == 100
    assert tail["span_share"] == pytest.approx(1e-4 / 0.02645, rel=0.01)
    assert tail["ms"]["99"] == pytest.approx(1e3 * harness.nearest_rank(
        gaps, 99))
    assert set(tail["ms"]) == {"98.5", "99", "99.5"}
    # on the slope between two modes it does not (PR 37's 95th percentile:
    # quantile 92 at 11.9 ms, 95 at 13.6 .. 15.7, 98 at 20.5 .. 24.1)
    slope = [0.0085] * 9200 + [0.0119 + 2e-5 * i for i in range(600)] \
        + [0.0258] * 200
    tail = harness.tail_in_mode(slope, 95, around=1.0)
    assert tail["inside_a_mode"] is False and tail["span_share"] > 0.2
    assert harness.tail_in_mode(slope, 99)["inside_a_mode"] is True
    # a mode that smears over 20 .. 27 ms holds the 99th percentile and not
    # its neighbours (gpt2xl-batch-decode, my chip runs, PR 38)
    smeared = [0.009] * 9800 + [0.020 + 3.5e-5 * i for i in range(200)]
    assert harness.tail_in_mode(smeared, 99)["inside_a_mode"] is False


def test_a_session_the_profiler_held_back_is_set_aside(capsys):
    """PR 24's fault: under the profiler ten steps took 4.8 s for 1.26 s
    of device work (idle 74 %) while the run's own window idled 0.2 %.
    The result line then carries the window's idle share, busy time and
    window, and no idle gaps of that session."""
    planes = _synthetic(step_s=0.1, steps=12, fetch_every=1, fetch_gap_s=0.3)
    reduced = trace_mod.reduce(planes, period=10)
    assert 100 * (1 - reduced["busy_s"] / reduced["window_s"]) > 70
    idle, _ = _idle(reduced, 358, 358 * 0.1 / 0.998, capsys)
    assert idle["differ"] is True
    assert idle["value"] == pytest.approx(0.2, abs=1e-6)
    from benchmark import run as run_mod

    account = {"correct": True, "attempted": 358, "failed": 0,
               "kind": "train-fed", "trace": reduced, "idle": idle,
               "device": {"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 1, "memory_peak_bytes": 1},
               "spans": {}, "counters": {}, "warmup_s": 1.0, "steps": 358,
               "window_s": 358 * 0.1 / 0.998,
               "cell": harness.load_cell("resnet50-fed"),
               "report": {"global_batch": 256}}
    line = json.loads(run_mod.result_of(account, trace=1))
    assert line["metrics"]["device_idle_share.train"]["value"] == \
        pytest.approx(0.2, abs=1e-6)
    assert line["device"]["busy_s"] == pytest.approx(35.8)
    assert line["device"]["window_s"] == pytest.approx(35.8 / 0.998)
    assert line["breakdown"]["idle_gaps"] == []      # a train session's
    assert line["breakdown"]["device_ops"]
    assert line["session_set_aside"] is True
    capsys.readouterr()


def test_recorded_tpu_trace_reduces_to_hand_summed_numbers():
    """A small trace recorded on a TPU v5e (``benchmark/data``): the
    reduction's busy time, window and per-operation sums equal sums made
    here by hand over the same events."""
    path = os.path.join(ROOT, "benchmark", "data", "small.xplane.pb")
    planes = trace_mod.load(path)
    reduced = trace_mod.reduce(planes)
    device = next(p for p in planes if p["name"] == "/device:TPU:0")
    lines = {line["name"]: sorted(line["events"], key=lambda e: e[1])
             for line in device["lines"]}
    runs = [e for e in lines["XLA Modules"]
            if e[0].startswith(reduced["main_program"] + "(")]
    assert reduced["steps"] == len(runs) - 1 >= 4
    t0, t1 = runs[0][1], runs[-1][1]
    assert reduced["window_s"] == pytest.approx(t1 - t0)
    busy, end = 0.0, t0
    for _, start, dur in lines["XLA Ops"]:
        a, b = max(start, end, t0), min(start + dur, t1)
        if b > a:
            busy += b - a
            end = b
    assert reduced["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    name, per_step = reduced["device_ops"][0]
    assert name.startswith(reduced["main_program"] + "/")
    total = sum(min(s + d, t1) - max(s, t0) for n, s, d in lines["XLA Ops"]
                if trace_mod.op_name(n) == name.split("/", 1)[1]
                and s + d > t0 and s < t1)
    assert per_step * reduced["steps"] == pytest.approx(total, rel=1e-9)
    assert sum(s for _, s in reduced["idle_gaps"]) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=0.05)
    assert any(n.startswith("bench/") for n, _ in reduced["idle_gaps"])


def test_op_names_are_short_and_stable():
    hlo = ("%fusion.94 = bf16[256,56,56,256]{3,0,2,1:T(8,128)(2,1)} "
           "fusion(bf16[1]{0} %p), kind=kLoop")
    assert trace_mod.op_name(hlo) == "fusion.94 bf16[256,56,56,256]"
    assert trace_mod.op_name("%copy-start.22 = (bf16[2,3]{1,0}, u32[]) "
                             "copy-start(...)") == "copy-start.22 bf16[2,3]"
    assert trace_mod.program_name("jit_step(8991787)") == "jit_step"


# ---------------------------------------------------------- peaks and shapes

def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


def test_shape_functions_count_the_published_models():
    resnet = harness.load_json("configs", "resnet50.json")
    work = shapes.resnet_train_step(resnet, 256)
    assert work["params"] == 25_557_032
    assert work["flops"] == pytest.approx(256 * 3 * 2 * 4.09e9, rel=0.01)
    gpt = harness.load_json("configs", "gpt2-xl.json")
    assert shapes.gpt_params(gpt)["all"] == pytest.approx(1.5576e9, rel=1e-3)
    step = shapes.gpt_decode_step(gpt, 16, 16 * 320)
    roof = shapes.roofline(step, harness.peaks_for("TPU v5 lite"), 0.245)
    assert roof["bound"] == "memory" and 1.0 < roof["share"] < 100.0


def test_memory_peak_is_the_fullest_chip():
    stats = [{"peak_bytes_in_use": 7e8, "bytes_in_use": 3e8,
              "peak_bytes_reserved": 8.6e9},
             {"peak_bytes_in_use": 13.2e9, "bytes_in_use": 8.2e9,
              "bytes_reserved": 4.0e9}]
    assert harness.memory_peak_bytes(stats) == 13.2e9
    assert harness.memory_peak_bytes(stats[:1]) == 8.9e9


# ------------------------------------------- the controls must fail the check

def test_no_configuration_of_the_manifest_loosens_a_reference_limit(
        manifest, capsys):
    """A configuration's own ``limits`` are for rehearsals at toy size.
    One named in ``BENCHMARK.json`` has none, or only tighter ones than
    its reference set from chip readings; and every compared number says
    where its limit came from."""
    for c in manifest["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        ref = harness.load_module("reference", cfg["reference"])
        for name, limit in cfg.get("limits", {}).items():
            assert name in ref.LIMITS and limit <= ref.LIMITS[name], \
                (c["name"], name)
    ref = harness.load_module("reference", "resnet50")
    toy = harness.load_json("configs", "toy-resnet.json")
    limits = harness.limits_for(ref.LIMITS, toy)
    assert limits["delta_norm_rel"] == [0.35, "configuration"]
    real = harness.limits_for(ref.LIMITS,
                              harness.load_json("configs", "resnet50.json"))
    assert real == {k: [v, "reference"] for k, v in ref.LIMITS.items()}
    checks = harness.Comparisons()
    checks.add("delta_norm_rel", 0.3, *limits["delta_norm_rel"])
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["limit_from"] == "configuration" and said["ok"] is True



def test_fp8_control_fails_the_training_comparison():
    """At the toy size (and its own limits, from readings at that size:
    bfloat16 rounding reads 0.06, fp8 0.3)."""
    ref = harness.load_module("reference", "resnet50")
    cfg = harness.load_json("configs", "toy-resnet.json")
    traffic = harness.load_json("traffic", "toy-fed.json")
    limits = {k: v for k, (v, _) in harness.limits_for(ref.LIMITS,
                                                       cfg).items()}
    batches = [traffic_gen.train_batch(42, j, traffic, cfg, 1)
               for j in range(3)]
    kw = dict(lr=cfg["learning_rate"], momentum=cfg["momentum"])
    sound = ref.first_steps(cfg, 42, batches, **kw)
    same = ref.compare(sound, sound)
    assert same["grad_norm_rel"] == 0.0 and same["loss_rel"] == 0.0
    control = ref.first_steps(cfg, 42, batches, quant="fp8", **kw)
    gap = ref.compare(control, sound)
    assert gap["grad_norm_rel"] > limits["grad_norm_rel"]
    assert math.isfinite(gap["loss_rel"])
    # a part of the batch left out (its second half repeats its first)
    # moves the loss past its limit, where fp8 does not
    half = [(np.concatenate([x[:4], x[:4]]), np.concatenate([y[:4], y[:4]]))
            for x, y in batches]
    part = ref.first_steps(cfg, 42, half, **kw)
    assert ref.compare(part, sound)["loss_rel"] > limits["loss_rel"] \
        > gap["loss_rel"]
    # a step that returns its state unchanged reads 1.0 in both norms
    frozen = dict(sound, grad_norms={k: 0.0 for k in sound["grad_norms"]},
                  delta_norms={k: 0.0 for k in sound["delta_norms"]})
    assert ref.compare(frozen, sound)["delta_norm_rel"] == 1.0


def test_fp8_control_fails_the_served_logit_comparison():
    """192 greedy positions of the toy model: bfloat16 rounding puts a
    token first that the reference has within 0.03 deviations of its
    best; fp8 one that is 0.5 or more below."""
    ref = harness.load_module("reference", "gpt2")
    cfg = dict(harness.load_json("configs", "toy-gpt.json"), dtype="float32")
    limits = {k: v for k, (v, _) in harness.limits_for(ref.LIMITS,
                                                       cfg).items()}
    import jax
    import jax.numpy as jnp

    stacked = jax.jit(lambda: ref.make_stacked(43, cfg))()
    forward = jax.jit(lambda s, ids: ref.forward(s, ids, cfg))
    rng = np.random.default_rng(43)
    rows, prompt, total = 4, 12, 60
    ids = np.zeros((rows, total), np.int32)
    ids[:, :prompt] = rng.integers(0, cfg["vocab_size"], (rows, prompt))
    for t in range(prompt, total):     # the reference's own greedy streams
        logits = forward(stacked, jnp.asarray(ids))
        ids[:, t] = np.asarray(logits[:, t - 1].argmax(-1))
    items = [(ids[r, :prompt], ids[r, prompt:]) for r in range(rows)]
    scored = ref.score(cfg, 43, items, control="fp8")
    assert scored["served_gap_sigmas"] <= 1e-3         # its own argmax
    assert scored["tokens"] == rows * (total - prompt)
    assert scored["control"]["served_gap_sigmas"] > \
        limits["served_gap_sigmas"]
    sound = ref.score(cfg, 43, items, control="bf16")["control"]
    assert sound["served_gap_sigmas"] < limits["served_gap_sigmas"]
