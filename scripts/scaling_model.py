"""Predicted 8->256-chip scaling efficiency from compiled collective traffic.

BASELINE.md row 2 ("Scaling efficiency, 8->256 chips, TPU v5e") cannot be
measured on this one-chip box, but it CAN be modeled from first principles
the way the scaling book prescribes: compile the real train step for each
mesh size, read the per-step collective bytes XLA actually emits out of the
partitioned HLO, and divide by an ICI bandwidth model.  The output is a
committed artifact (``bench_artifacts/scaling_model.json``) with every
assumption stated — a prediction to be validated on a pod, not a claim of
measurement.

Method, per mesh size n in {8..256}:

1. spawn a child with ``--xla_force_host_platform_device_count=n`` (virtual
   CPU devices; GSPMD partitioning is identical to real chips — the SPMD
   partitioner sees only the mesh, never the transport);
2. jit + compile the train step exactly as the framework runs it
   (``donate_argnums``, same shardings);
3. parse the optimized HLO for collectives (all-reduce / all-gather /
   reduce-scatter / all-to-all / collective-permute, sync and async forms),
   take each op's payload bytes and replica group, and classify which mesh
   AXES the group spans by unraveling member device ids to mesh coordinates;
4. model each collective's time on a v5e 2D-torus pod (assumptions in
   ``MODEL_ASSUMPTIONS``) and combine with compute time from XLA's
   ``cost_analysis`` FLOPs at the last measured MFU.

Workloads: the north-star ResNet-50 data-parallel step (pure dp — gradient
all-reduce is the only traffic) and the flagship BERT GSPMD step from
``__graft_entry__`` (tp2·sp2 inside a host, dp across hosts).

Usage: ``python scripts/scaling_model.py`` (parent; ~minutes — one XLA CPU
compile per (workload, n)); ``--child`` is internal.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import re
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MESH_SIZES = [8, 16, 32, 64, 128, 256]

# ---------------------------------------------------------------------------
# Bandwidth / topology model (STATED ASSUMPTIONS — the artifact embeds these)
# ---------------------------------------------------------------------------
# the row _build_resnet_dp models: per-chip batch 256, conv7 stem, bf16 BN
# — the TUNED config (r5: bf16 BN is +27.7% and is what a real dp run
# would deploy; gradient/collective bytes are BN-dtype-independent, so
# only the MFU anchor moves).  Shared with
# scripts/validate_scaling_model.py so the anchor and the validation can
# never silently select different rows.
def IS_MODELED_RESNET(r):
    return (r.get("batch") == 256 and r.get("stem") == "conv7"
            and r.get("bn") == "bf16")


def measured_rows(artifact_name: str) -> list:
    """Committed on-chip eager rows (no remat/loop) with an MFU — the
    single row-selection predicate for MFU anchoring AND validation."""
    with open(os.path.join(REPO, "bench_artifacts", artifact_name)) as f:
        return [r for r in json.load(f)["rows"]
                if "TPU" in str(r.get("device", "")) and r.get("mfu")
                and not r.get("loop") and not r.get("remat")]


def best_measured_row(artifact_name: str, prefer=None):
    """Config-matched row when available (``prefer``), else best-MFU —
    the workloads model a specific per-chip batch, so the matched row's
    MFU is the right anchor when it exists."""
    rows = measured_rows(artifact_name)
    if prefer is not None:
        matched = [r for r in rows if prefer(r)]
        if matched:
            rows = matched
    return max(rows, key=lambda r: r["mfu"]) if rows else None


def _anchor_mfu():
    """MFU table for t_compute, anchored on the best committed on-chip
    measurement available at run time: conv workloads on
    ``bench_artifacts/resnet_sweep.json``, transformer workloads on
    ``bench_artifacts/gpt_train_sweep.json`` once the ``gpt_train`` sweep
    stages have run on-chip (VERDICT r3 item 3).  Until a transformer
    measurement exists the transformer rows fall back to the measured
    ResNet MFU — the fallback is flagged in ``mfu_provenance`` so the
    artifact can never silently present the proxy as a measurement."""
    conv = xfmr = 0.24  # 2026-07-29 on-chip ResNet b256 bf16
    prov = {"conv": "default 0.24 (measured 2026-07-29, b256 bf16)",
            "transformer": "ASSUMED = conv MFU; no on-chip transformer "
                           "measurement committed yet (gpt_train sweep "
                           "stages pending)"}
    try:
        # _build_resnet_dp models per-chip batch 256 with the conv7 stem
        r = best_measured_row("resnet_sweep.json", prefer=IS_MODELED_RESNET)
        if r:
            # build the provenance text BEFORE assigning the value so a
            # malformed row can never leave a measured number in the
            # table with proxy provenance
            text = (f"measured {r['mfu']} (resnet_sweep.json "
                    f"b{r.get('batch')} {r.get('stem')} bn={r.get('bn')})")
            conv = xfmr = r["mfu"]  # xfmr: proxy until a gpt row lands
            prov["conv"] = text
    except (OSError, ValueError, KeyError):
        pass
    try:
        r = best_measured_row("gpt_train_sweep.json")
        if r:
            text = (f"measured {r['mfu']} (gpt_train_sweep.json "
                    f"b{r.get('batch')} T{r.get('seq')} "
                    f"attn={r.get('attn', 'dense')})")
            xfmr = r["mfu"]
            prov["transformer"] = text
    except (OSError, ValueError, KeyError):
        pass
    table = {
        "resnet50_dp": conv, "resnet50_dp_2slice": conv,
        "bert_tp_sp_dp": xfmr, "bert_fsdp8_dp": xfmr,
        "bert_fsdp8_2slice": xfmr,
        "ring_longctx_sp": xfmr, "ring_longctx_sp_t8k": xfmr,
        "ring16_sp_t8k": xfmr, "ulysses16_sp_t8k": xfmr,
        "moe_ep8_dp": xfmr, "gpipe_pp8_dp": xfmr, "gpipe_pp8_2slice": xfmr,
        "pp8_1f1b_m64_dp": xfmr,
    }
    return table, prov


_MFU_TABLE, _MFU_PROVENANCE = _anchor_mfu()

MODEL_ASSUMPTIONS = {
    "topology": "TPU v5e pod, 2D ICI torus 16x16 (256 chips, one pod; no "
                "DCN inside the modeled range).  The *_2slice workloads "
                "model TPU Multislice instead (meshes built by "
                "parallel.make_hybrid_mesh): resnet50_dp_2slice crosses "
                "DCN on dp, gpipe_pp8_2slice on pp (4 contiguous stages "
                "per slice), bert_fsdp8_2slice on fsdp (the deliberate "
                "anti-pattern probe)",
    "ici_GBps_per_link_per_direction": 45.0,
    "ici_links_per_axis": 1,       # one link each way along each torus axis
    "torus_axes": 2,               # a full-pod axis can ring over both
    "dcn_GBps_per_chip_per_direction": 6.25,
    "dcn_note": "per-chip share of slice DCN egress, assuming 50 GB/s per "
                "8-chip v5e host (4x100 GbE); cross-slice collectives are "
                "priced hierarchically — ICI phases at full group width, "
                "the cross-slice phase on 1/k_ici of the payload at "
                "per-chip DCN bandwidth (the standard multislice "
                "reduce-scatter / DCN-transfer / all-gather decomposition)",
    "peak_bf16_flops_per_chip": 197e12,
    # anchored on committed on-chip artifacts at run time (_anchor_mfu);
    # mfu_provenance records measurement vs proxy per workload family
    "mfu": _MFU_TABLE,
    "mfu_provenance": _MFU_PROVENANCE,
    "loop_collectives": "a collective inside a while-loop body appears "
                        "once in HLO but runs trip-count times; each "
                        "loop's trip is read from the constant bound in "
                        "its condition computation (lax.scan/fori emit "
                        "counted loops; ring K/V rotation = sp trips, "
                        "chunked-xent scan = ceil(V/chunk)), nested "
                        "loops multiply, and a loop with no parseable "
                        "bound and no declared fallback is an error — "
                        "never a silent undercount",
    "loop_flops": "cost_analysis also counts while-body FLOPs once; "
                  "body DOT flops (2*out_elems*contracted_extent) are "
                  "re-added x(trip-1) from the HLO — elementwise body "
                  "flops remain counted once (negligible next to the "
                  "dots in these workloads)",
    "collective_models": {
        "all-reduce": "2*bytes*(k-1)/k / BW   (bidirectional ring, "
                      "reduce-scatter + all-gather phases)",
        "reduce-scatter": "bytes*(k-1)/k / BW",
        "all-gather": "bytes*(k-1)/k / BW",
        "all-to-all": "bytes*(k-1)/k / BW (payload = largest operand)",
        "collective-permute": "bytes / BW (one hop)",
    },
    "axis_bandwidth": "BW = ici_GBps * 2 directions * torus_axes_used; "
                      "an axis spanning >=16 chips uses both torus axes, "
                      "smaller axes one",
    "overlap": "two bounds reported: none (t_c + t_comm) and full "
               "(max(t_c, t_comm)); real overlap lands between",
    "excluded": "host input pipeline, DCN, stragglers, XLA latency-hiding "
                "scheduler specifics, per-collective latency floors",
}


def axis_bw_GBps(k: int) -> float:
    a = MODEL_ASSUMPTIONS
    axes = a["torus_axes"] if k >= 16 else 1
    return a["ici_GBps_per_link_per_direction"] * 2 * axes


def collective_time_s(op: str, bytes_: float, k: int,
                      dcn: dict | None = None) -> float:
    if k <= 1:
        return 0.0
    if dcn:
        # Cross-slice group: hierarchical decomposition (see "dcn_note").
        # ICI phases run at the in-slice width k_ici; the cross-slice
        # phase moves each chip's 1/k_ici shard over per-chip DCN.
        ki, kd = dcn["k_ici"], dcn["k_dcn"]
        bw_i = axis_bw_GBps(ki) * 1e9
        bw_d = MODEL_ASSUMPTIONS["dcn_GBps_per_chip_per_direction"] * 1e9
        shard = bytes_ / max(ki, 1)
        if op == "all-reduce":
            # in-slice reduce-scatter + all-gather, cross-slice all-reduce
            ici = 2 * bytes_ * (ki - 1) / ki / bw_i if ki > 1 else 0.0
            return ici + 2 * shard * (kd - 1) / kd / bw_d
        if op in ("reduce-scatter", "all-gather"):
            ici = bytes_ * (ki - 1) / ki / bw_i if ki > 1 else 0.0
            return ici + shard * (kd - 1) / kd / bw_d
        if op == "all-to-all":
            # (kd-1)/kd of the payload crosses slices; the rest stays ICI
            return (bytes_ * (kd - 1) / kd / bw_d
                    + (bytes_ / kd) * (ki - 1) / max(ki, 1) / bw_i)
        if op == "collective-permute":
            return bytes_ / bw_d  # the modeled hop crosses slices
        raise ValueError(f"unmodeled collective op {op!r}")
    bw = axis_bw_GBps(k) * 1e9
    if op == "all-reduce":
        return 2 * bytes_ * (k - 1) / k / bw
    if op in ("reduce-scatter", "all-gather", "all-to-all"):
        return bytes_ * (k - 1) / k / bw
    if op == "collective-permute":
        return bytes_ / bw  # one hop
    raise ValueError(f"unmodeled collective op {op!r}")


# ---------------------------------------------------------------------------
# HLO collective extraction (child side)
# ---------------------------------------------------------------------------
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\((?:[^()]|\([^()]*\))*\)|\S+)\s+"  # type: tuple (1 nesting) or scalar
    r"(all-reduce-start|all-reduce|all-gather-start|all-gather|"
    r"reduce-scatter-start|reduce-scatter|"
    r"collective-permute-start|collective-permute|"
    r"all-to-all-start|all-to-all)\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_PERMUTE_RE = re.compile(r"source_target_pairs=\{\{(\d+),(\d+)\}")
_PERMUTE_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")


def _shape_bytes(type_str: str) -> float:
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _tuple_elements(type_str: str) -> list[str]:
    """Split a tuple type ``(f32[8,2]{1,0}, (f32[4]), u32[])`` at its TOP
    level — commas inside ``[]``/``{}``/nested ``()`` don't split."""
    s = type_str.strip()
    if not (s.startswith("(") and s.endswith(")")):
        return [s]
    s = s[1:-1]
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(s):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            parts.append(s[start:i])
            start = i + 1
    parts.append(s[start:])
    return [p for p in (p.strip() for p in parts) if p]


def _payload_bytes(type_str: str, is_async_start: bool) -> float:
    """Collective payload from the HLO result type.  Sync forms: the whole
    (possibly variadic-tuple) result IS the payload.  Async ``-start``
    forms return ``(operand, result[, context scalars...])`` — counting
    the whole tuple would double the payload, so take the result element."""
    if not is_async_start:
        return _shape_bytes(type_str)
    elems = _tuple_elements(type_str)
    if len(elems) >= 2:
        return _shape_bytes(elems[1])
    return _shape_bytes(elems[0])


def _first_group(line: str, n_devices: int):
    """First replica group's device ids, handling explicit, iota, and
    empty (= all devices) forms.  Raises on anything else — a silently
    unpriced collective would inflate the predicted efficiency."""
    m = _GROUPS_RE.search(line)
    if m:
        return [int(v) for v in m.group(1).split(",")]
    m = _IOTA_RE.search(line)
    if m:
        import numpy as np

        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(v) for v in m.group(3).split(",")]
        ids = np.arange(math.prod(dims)).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(v) for v in m.group(4).split(",")])
        return list(ids.reshape(n_groups, group_size)[0])
    if "replica_groups={}" in line:  # empty form: one group of everyone
        return list(range(n_devices))
    return None


# a computation definition line: `%name (args...) -> type {` — args/types
# nest parens freely, so anchor on the NAME-then-( prefix and the `{` tail
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_WHILE_RE = re.compile(r"while\([^)]*\),\s*condition=%?([\w.\-]+),\s*"
                       r"body=%?([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        m = _COMPUTATION_RE.match(line.strip())
        if m:
            cur = m.group(1)
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)
    return comps


_CALLEE_RE = re.compile(
    r"(?:calls=|to_apply=|true_computation=|false_computation=)"
    r"%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def _loop_multipliers(comps: dict[str, list[str]],
                      fallback_trip: int | None) -> dict[str, int]:
    """Execution-count multiplier per computation.

    A collective (or dot) in a ``while`` body runs trip-count times but
    appears once in HLO.  XLA emits counted loops (``lax.scan`` /
    ``fori_loop``, and its own pipelined 'wide' transforms of them) with
    the bound as a constant in the CONDITION computation — read it there
    (largest constant = the ascending bound); nested whiles multiply.
    Multipliers ALSO flow through plain call edges (fusions' ``calls=``,
    ``to_apply=`` reducers, conditional branches) so an op the compiler
    moved into a sub-computation of a loop body is still scaled; a
    computation reachable from several callers takes the MAX multiplier
    (conservative over-count, never an undercount).  A while body whose
    condition has no usable constant falls back to ``fallback_trip``;
    ``None`` fallback raises so traffic is never silently underpriced.
    """
    # edges: callee -> list of (caller, factor)
    edges: dict[str, list[tuple[str, str | None]]] = {}
    for parent, lines in comps.items():
        for line in lines:
            for cond, body in _WHILE_RE.findall(line):
                edges.setdefault(body, []).append((parent, cond))
                edges.setdefault(cond, []).append((parent, None))
            for callee in _CALLEE_RE.findall(line):
                edges.setdefault(callee, []).append((parent, None))
            for m in _BRANCHES_RE.finditer(line):
                for callee in re.findall(r"%?([\w.\-]+)", m.group(1)):
                    edges.setdefault(callee, []).append((parent, None))

    def trip_of(cond: str) -> int | None:
        consts = [int(v) for v in _CONST_RE.findall(
            "\n".join(comps.get(cond, [])))]
        best = max(consts, default=0)
        return best if best > 0 else fallback_trip

    mult: dict[str, int] = {}

    def resolve(comp: str, seen=()) -> int:
        if comp in mult:
            return mult[comp]
        if comp in seen:  # cycle guard (should not happen in HLO)
            return 1
        m = 1
        for parent, cond in edges.get(comp, ()):
            factor = 1
            if cond is not None:  # comp is this while's BODY
                trip = trip_of(cond)
                if trip is None:
                    raise ValueError(
                        f"while body {comp!r}: no trip-count constant in "
                        f"condition {cond!r} and no fallback declared — "
                        f"in-loop collectives would be underpriced")
                factor = trip
            m = max(m, factor * resolve(parent, (*seen, comp)))
        mult[comp] = m
        return m

    for comp in comps:
        resolve(comp)
    return mult


_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\w+\[[\d,]*\])")
_DOT_LINE_RE = re.compile(
    r"=\s*(\w+\[[\d,]*\])\S*\s+dot\(\s*%?([\w.\-]+)\s*,\s*%?([\w.\-]+)")
_CONTRACT_RE = re.compile(r"rhs_contracting_dims=\{([\d,]*)\}")


def _dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m or not m.group(2):
        return []
    return [int(v) for v in m.group(2).split(",")]


def _loop_dot_flops(comps: dict[str, list[str]],
                    mult: dict[str, int]) -> float:
    """Extra matmul FLOPs hidden by loops: XLA's ``cost_analysis`` counts
    a while body's FLOPs once, but the body runs trip-count times — the
    same undercount the collective extractor corrects for bytes.  Dots
    dominate (ring attention blocks, xent chunk matmuls); elementwise
    body FLOPs stay undercounted and are noted in the assumptions.

    dot FLOPs = 2 × result_elements × contracted_extent.  Operand types
    are not printed inline, so each computation's instruction definitions
    (``%name = type ...``) form a local symbol table the rhs shape is
    resolved from.  Returns Σ body-dot FLOPs × (multiplier − 1), to be
    added to ``cost_analysis``'s total (which priced each body once).
    """
    extra = 0.0
    for comp, m in mult.items():
        if m <= 1:
            continue
        table = {}
        for line in comps.get(comp, []):
            im = _INSTR_RE.match(line)
            if im:
                table[im.group(1)] = im.group(2)
        for line in comps.get(comp, []):
            dm = _DOT_LINE_RE.search(line)
            if not dm:
                continue
            out_elems = math.prod(_dims(dm.group(1))) or 1
            cm = _CONTRACT_RE.search(line)
            rhs_type = table.get(dm.group(3))
            if not cm or rhs_type is None:
                continue  # conservative: skip rather than guess
            rhs_dims = _dims(rhs_type)
            contract = 1
            for idx in (int(v) for v in cm.group(1).split(",") if v):
                if idx < len(rhs_dims):
                    contract *= rhs_dims[idx]
            extra += 2.0 * out_elems * contract * (m - 1)
    return extra


def extract_collectives(hlo: str, axis_sizes: dict,
                        loop_trip: int | None = None,
                        comps: dict | None = None,
                        mult: dict | None = None,
                        dcn_extents: dict | None = None) -> list[dict]:
    """One record per collective op in the partitioned module: payload
    bytes (already multiplied by the enclosing loops' trip counts — see
    :func:`_loop_multipliers`), group size, and which mesh axes the
    group spans.  Pass precomputed ``comps``/``mult`` to avoid re-parsing
    a large HLO text (the 2M-token ring modules run to hundreds of MB).

    ``dcn_extents`` (multislice workloads): ``{axis: (k_dcn, k_ici)}`` for
    every axis whose extent is dcn-major split across slices (the
    ``make_hybrid_mesh`` layout).  A group whose coordinates on such an
    axis cross a slice boundary gets a ``"dcn": {k_dcn, k_ici}`` field so
    the pricing model can decompose it hierarchically."""
    import numpy as np

    sizes = tuple(axis_sizes.values())
    names = list(axis_sizes.keys())
    if comps is None:
        comps = _split_computations(hlo)
    if mult is None:
        mult = _loop_multipliers(comps, loop_trip)
    out = []
    for comp, lines in comps.items():
        for line in lines:
            m = _OP_RE.search(line)
            if not m:
                continue
            raw_op = m.group(2)
            type_str, op = m.group(1), raw_op.removesuffix("-start")
            bytes_ = _payload_bytes(type_str, raw_op.endswith("-start"))
            # (all-gather payload is counted at the gathered size: the
            # result type is the full gather)
            bytes_ *= mult[comp]
            total = math.prod(sizes)
            group = _first_group(line, total)
            if group is None and op == "collective-permute":
                pm = _PERMUTE_RE.search(line)
                group = [int(pm.group(1)), int(pm.group(2))] if pm else None
            if not group:
                raise ValueError(
                    f"unparseable replica_groups in collective: {line!r}")
            if op == "reduce-scatter":
                # the HLO result type is the SCATTERED 1/k shard; the ring
                # formula bytes*(k-1)/k prices the full pre-scatter input
                # (all-gather needs no correction — its result IS the full
                # gathered shape)
                bytes_ *= len(group)
            coords = np.array(np.unravel_index(np.array(group), sizes)).T
            axes = [names[i] for i in range(len(names))
                    if len(set(coords[:, i])) > 1]
            rec = {"op": op, "bytes": bytes_,
                   "group_size": len(group), "axes": axes,
                   "loop_multiplier": mult[comp]}
            if dcn_extents:
                def sid(row):
                    # slice id = the dcn-major block along every
                    # slice-split axis of the make_hybrid_mesh layout
                    return tuple(
                        row[names.index(ax)] // ici_k
                        for ax, (_dcn_k, ici_k) in sorted(dcn_extents.items()))

                if op == "collective-permute":
                    # Hops run in parallel, so ONE cross-slice pair makes
                    # DCN the op's bottleneck — classify from ALL pairs,
                    # not the first (pairs are not symmetric like replica
                    # groups).
                    pm = _PERMUTE_PAIRS_RE.search(line)
                    pairs = ([tuple(map(int, p)) for p in re.findall(
                        r"\{(\d+),(\d+)\}", pm.group(1))]
                        if pm else [tuple(group)])
                    crosses = any(
                        sid(np.unravel_index(a, sizes))
                        != sid(np.unravel_index(b, sizes))
                        for a, b in pairs)
                    if crosses:
                        # k_dcn = total slice count (pricing only uses
                        # bytes/bw_d for permutes, but the metadata must
                        # not hardcode 2); a hop links exactly 2 devices
                        rec["dcn"] = {"k_dcn": math.prod(
                            d for d, _ in dcn_extents.values()),
                            "k_ici": 1}
                else:
                    # >1 distinct slice id among members -> crosses DCN
                    slice_ids = {sid(row) for row in coords}
                    k_dcn = len(slice_ids)
                    if k_dcn > 1:
                        rec["dcn"] = {"k_dcn": k_dcn,
                                      "k_ici": len(group) // k_dcn}
            out.append(rec)
    return out


# ---------------------------------------------------------------------------
# Workload builders (child side)
# ---------------------------------------------------------------------------
def _hybrid(n: int, ici: dict, dcn: dict):
    """Build the 2+-slice hybrid mesh AND the matching ``dcn_extents``
    from one spec, so the slice boundary used for mesh layout and the one
    used for collective classification can never drift apart."""
    import math as _math

    import jax

    from tensorflowonspark_tpu.parallel import make_hybrid_mesh

    slices = _math.prod(dcn.values())
    per = n // slices
    mesh = make_hybrid_mesh(ici=ici, dcn=dcn, devices=jax.devices()[:n],
                            slice_key=lambda d: d.id // per)
    extents = {ax: (dcn[ax], ici.get(ax, 1)) for ax in dcn}
    return mesh, extents


def _build_resnet_dp(n: int, slices: int = 1):
    """North-star workload: ResNet-50, pure data parallel, bf16, per-chip
    batch 256 (the measured bench configuration).  ``slices=2`` builds the
    TPU-Multislice variant instead: the same step over a
    ``make_hybrid_mesh`` whose dp axis is dcn-major across 2 slices, so
    the gradient all-reduce is priced hierarchically (ICI + DCN)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorflowonspark_tpu.models.resnet import ResNet50
    from tensorflowonspark_tpu.parallel import make_mesh
    from tensorflowonspark_tpu.parallel.mesh import MeshSpec

    dcn_extents = None
    if slices > 1:
        mesh, dcn_extents = _hybrid(n, ici=dict(dp=n // slices),
                                    dcn=dict(dp=slices))
    else:
        mesh = make_mesh(MeshSpec(dp=n), devices=jax.devices()[:n])
    model = ResNet50()
    per_chip = 256
    batch = per_chip * n
    image = 224
    x = jax.ShapeDtypeStruct((batch, image, image, 3), jnp.bfloat16)
    y = jax.ShapeDtypeStruct((batch,), jnp.int32)
    tx = optax.sgd(0.1, momentum=0.9)

    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, image, image, 3), jnp.bfloat16),
                           train=True))
    abstract_opt = jax.eval_shape(tx.init, variables["params"])
    rep = NamedSharding(mesh, P())
    var_sh = jax.tree.map(lambda _: rep, variables)
    opt_sh = jax.tree.map(lambda _: rep, abstract_opt)
    data_sh = NamedSharding(mesh, P("dp"))

    def train_step(variables, opt_state, x, y):
        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": variables["batch_stats"]},
                x, train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, updates

        (loss, updates), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(variables["params"])
        upd, opt_state = tx.update(grads, opt_state, variables["params"])
        params = optax.apply_updates(variables["params"], upd)
        return ({"params": params,
                 "batch_stats": updates["batch_stats"]}, opt_state, loss)

    jitted = jax.jit(
        train_step, donate_argnums=(0, 1),
        in_shardings=(var_sh, opt_sh, data_sh, data_sh))
    if dcn_extents:
        return mesh, jitted, (variables, abstract_opt, x, y), 1, dcn_extents
    return mesh, jitted, (variables, abstract_opt, x, y), 1


def _build_bert_gspmd(n: int):
    """Flagship workload: THE dryrun train step (``__graft_entry__.
    build_bert_train_step`` — same loss, same shardings, same donation)
    at BERT-base dims: tp2·sp2 inside a host, dp = n/4 across, ring
    attention over sp, chunked tied xent, adamw."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from __graft_entry__ import build_bert_train_step
    from tensorflowonspark_tpu.models import BertConfig
    from tensorflowonspark_tpu.parallel import make_mesh, ring_self_attention
    from tensorflowonspark_tpu.parallel.mesh import MeshSpec

    mesh = make_mesh(MeshSpec(dp=n // 4, sp=2, tp=2),
                     devices=jax.devices()[:n])
    cfg = BertConfig(num_layers=12, hidden_size=768, num_heads=12,
                     intermediate_size=3072, max_position_embeddings=512,
                     dtype=jnp.bfloat16, dropout_rate=0.0,
                     attention_fn=partial(ring_self_attention, mesh),
                     emb_spec=(("ep", "tp"), None))
    per_chip_batch = 8           # per-dp-group batch; global = 8 * dp
    built = build_bert_train_step(
        mesh, cfg, chunk_size=4096,
        batch=per_chip_batch * mesh.shape["dp"], seq=512)
    batch, seq = built["batch"], built["seq"]
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    labels = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    # ring attention's K/V rotation is a fori_loop over the sp axis
    return mesh, built["step"], (*built["abstract"], ids, labels), \
        mesh.shape["sp"]


def _build_bert_fsdp(n: int, slices: int = 1):
    """ZeRO-3 regime: BERT-base with weights auto-sharded over fsdp=8
    inside a host (the dryrun phase-4 overlay), dp = n/8 across — the
    traffic is per-layer weight all-gathers + grad reduce-scatters, the
    scaling question FSDP users actually have.

    ``slices=2`` is the deliberate ANTI-PATTERN probe: fsdp dcn-major
    across 2 slices, so every per-layer weight all-gather and grad
    reduce-scatter crosses DCN — pricing exactly what the scaling guide
    tells users not to do, so the advice carries a number."""
    import jax
    import jax.numpy as jnp

    from __graft_entry__ import auto_fsdp_overlay, build_bert_train_step
    from tensorflowonspark_tpu.models import BertConfig
    from tensorflowonspark_tpu.parallel import make_mesh
    from tensorflowonspark_tpu.parallel.mesh import MeshSpec

    dcn_extents = None
    if slices > 1:
        mesh, dcn_extents = _hybrid(
            n, ici=dict(fsdp=8 // slices, dp=n // 8),
            dcn=dict(fsdp=slices))
    else:
        mesh = make_mesh(MeshSpec(dp=n // 8, fsdp=8),
                         devices=jax.devices()[:n])
    cfg = BertConfig(num_layers=12, hidden_size=768, num_heads=12,
                     intermediate_size=3072, max_position_embeddings=512,
                     dtype=jnp.bfloat16, dropout_rate=0.0)
    built = build_bert_train_step(
        mesh, cfg, chunk_size=4096, batch=8 * n, seq=512,
        shard_overlay=auto_fsdp_overlay(mesh))
    batch, seq = built["batch"], built["seq"]
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    labels = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    if dcn_extents:
        return (mesh, built["step"], (*built["abstract"], ids, labels), 1,
                dcn_extents)
    return mesh, built["step"], (*built["abstract"], ids, labels), 1


def _build_ring_longctx(n: int, per_device_seq: int = 2048):
    """Long-context regime: sequence sharded over sp = ALL n devices with
    ring attention, ``per_device_seq`` tokens per device (T grows with
    the mesh — 524k tokens at n=256·2048), batch 1.  Prices the brief's
    long-context-first-class claim: K/V blocks rotate (sp hops per layer,
    again on the backward).  The per-device shard size is THE efficiency
    knob: ring comm per device is O(T_total) while attention compute per
    device is O(T_local·T_total), so efficiency scales with T_local."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from __graft_entry__ import build_bert_train_step
    from tensorflowonspark_tpu.models import BertConfig
    from tensorflowonspark_tpu.parallel import make_mesh, ring_self_attention
    from tensorflowonspark_tpu.parallel.mesh import MeshSpec

    mesh = make_mesh(MeshSpec(sp=n, dp=1), devices=jax.devices()[:n])
    seq = per_device_seq * n
    cfg = BertConfig(num_layers=12, hidden_size=768, num_heads=12,
                     intermediate_size=3072, max_position_embeddings=seq,
                     dtype=jnp.bfloat16, dropout_rate=0.0,
                     attention_fn=partial(ring_self_attention, mesh))
    built = build_bert_train_step(mesh, cfg, chunk_size=4096, batch=1,
                                  seq=seq)
    ids = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    labels = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    return mesh, built["step"], (*built["abstract"], ids, labels), \
        mesh.shape["sp"]


def _build_sp_attn_h16(n: int, impl: str):
    """Ring vs Ulysses, exact apples-to-apples: identical model (16 heads
    so Ulysses can shard sp=16, hidden 1024, 12 layers), identical mesh
    (sp=n), identical 8192 tokens/device — only the sequence-parallel
    attention construction differs.  Prices the docs/scaling.md guidance
    ("long-and-thin → ring; wide → Ulysses") instead of asserting it.
    Ulysses caps sp at num_heads, so these run only at n ≤ 16 — that cap
    IS one of the findings."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from __graft_entry__ import build_bert_train_step
    from tensorflowonspark_tpu.models import BertConfig
    from tensorflowonspark_tpu.parallel import (make_mesh,
                                                ring_self_attention,
                                                ulysses_self_attention)
    from tensorflowonspark_tpu.parallel.mesh import MeshSpec

    mesh = make_mesh(MeshSpec(sp=n, dp=1), devices=jax.devices()[:n])
    attn = {"ring": ring_self_attention,
            "ulysses": ulysses_self_attention}[impl]
    seq = 8192 * n
    cfg = BertConfig(num_layers=12, hidden_size=1024, num_heads=16,
                     intermediate_size=4096, max_position_embeddings=seq,
                     dtype=jnp.bfloat16, dropout_rate=0.0,
                     attention_fn=partial(attn, mesh))
    built = build_bert_train_step(mesh, cfg, chunk_size=4096, batch=1,
                                  seq=seq)
    ids = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    labels = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    trip = mesh.shape["sp"] if impl == "ring" else None
    return mesh, built["step"], (*built["abstract"], ids, labels), trip


def _build_moe_ep8(n: int):
    """Expert parallelism: 8 experts sharded over ep=8, dp = n/8, the
    all_to_all dispatch path (``parallel/moe.py``) in a full train step —
    GShard-style traffic: two all_to_alls (dispatch + return) per layer
    over the ep axis, constant per device as dp grows."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorflowonspark_tpu.parallel import (make_mesh, make_moe_layer,
                                                moe_apply)
    from tensorflowonspark_tpu.parallel.mesh import MeshSpec

    mesh = make_mesh(MeshSpec(ep=8, dp=n // 8), devices=jax.devices()[:n])
    hidden, ffn, experts = 768, 3072, 8
    moe_fn, init_fn, specs = make_moe_layer(hidden, ffn, experts,
                                            top_k=2, ep=8,
                                            dtype=jnp.bfloat16)
    tx = optax.adam(1e-3)
    tokens = 2048 * n  # 2048 tokens per device
    x = jax.ShapeDtypeStruct((tokens, hidden), jnp.bfloat16)

    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda s: isinstance(s, P))
    abstract_params = jax.eval_shape(lambda: init_fn(jax.random.key(0)))
    abstract_opt = jax.eval_shape(tx.init, abstract_params)
    # adam state mirrors params: leave unconstrained, propagation mirrors
    data_sh = NamedSharding(mesh, P(("dp", "fsdp", "ep"), None))

    def loss_fn(p, x):
        y, aux = moe_apply(mesh, moe_fn, p, x, param_specs=specs)
        return jnp.mean(y ** 2) + 0.01 * aux

    def train_step(p, o, x):
        loss, grads = jax.value_and_grad(loss_fn)(p, x)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    jitted = jax.jit(train_step, donate_argnums=(0, 1),
                     in_shardings=(shardings, None, data_sh))
    return mesh, jitted, (abstract_params, abstract_opt, x), None


def _build_pipeline_pp8(n: int, slices: int = 1):
    """Pipeline parallelism: 8 GPipe stages over pp=8, dp = n/8 — the
    manual shard_map schedule (``parallel/pipeline.py``) with BERT-base
    transformer stages; traffic is one activation tensor per microbatch
    per stage hop, the cheapest bytes/step of any axis.

    ``slices=2``: the docs' recommended multislice layout — pp dcn-major
    across 2 slices (4 contiguous stages per slice), so the mid-pipeline
    hop and the ring wrap cross DCN while dp's gradient all-reduce and
    the in-slice stage hops stay on ICI."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorflowonspark_tpu.parallel import (make_mesh, pipeline_apply,
                                                make_transformer_stage,
                                                stack_stage_params)
    from tensorflowonspark_tpu.parallel.mesh import MeshSpec

    dcn_extents = None
    if slices > 1:
        mesh, dcn_extents = _hybrid(n, ici=dict(pp=8 // slices, dp=n // 8),
                                    dcn=dict(pp=slices))
    else:
        mesh = make_mesh(MeshSpec(pp=8, dp=n // 8), devices=jax.devices()[:n])
    hidden, heads, ffn, seq, vocab = 768, 12, 3072, 512, 32768
    num_mb = 16
    batch = 2 * num_mb * mesh.shape["dp"]
    stage_fn, init_fn, param_specs = make_transformer_stage(
        hidden, heads, ffn, tp=1, causal=True, dtype=jnp.bfloat16)
    tx = optax.adamw(1e-4)
    data_spec = P(("dp", "fsdp"), "sp", None)  # sp=1; spec keeps the ring
    # carries' varying-axes annotation consistent (as the dryrun does)

    def init_params():
        keys = jax.random.split(jax.random.key(0), 8)
        return {
            "emb": (jax.random.normal(jax.random.key(1), (vocab, hidden))
                    * 0.02).astype(jnp.bfloat16),
            "stages": stack_stage_params([init_fn(k) for k in keys]),
        }

    p_sh = {
        "emb": NamedSharding(mesh, P()),
        "stages": jax.tree.map(
            lambda s: NamedSharding(mesh, P("pp", *s)), param_specs,
            is_leaf=lambda s: isinstance(s, P)),
    }
    abstract_params = jax.eval_shape(init_params)
    abstract_opt = jax.eval_shape(tx.init, abstract_params)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    def loss_fn(p, ids):
        x = p["emb"][ids]
        y = pipeline_apply(mesh, stage_fn, p["stages"], x,
                           num_microbatches=num_mb,
                           param_specs=param_specs, data_spec=data_spec)
        logits = jnp.einsum("bsh,vh->bsv", y, p["emb"])
        labels = jnp.roll(ids, -1, axis=1)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    def train_step(p, o, ids):
        loss, grads = jax.value_and_grad(loss_fn)(p, ids)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    jitted = jax.jit(
        train_step, donate_argnums=(0, 1),
        in_shardings=(p_sh, None,
                      NamedSharding(mesh, P(("dp", "fsdp"), None))))
    # GPipe microbatch schedule loops; bound parsed from HLO conditions,
    # fallback = the schedule length if a condition is unreadable
    trip = num_mb + mesh.shape["pp"] - 1
    if dcn_extents:
        return (mesh, jitted, (abstract_params, abstract_opt, ids), trip,
                dcn_extents)
    return mesh, jitted, (abstract_params, abstract_opt, ids), trip


def _build_pipeline_pp8_1f1b(n: int):
    """The interleaved (1F1B-style) schedule at 4x GPipe's microbatches:
    ``pipeline_value_and_grad`` holds only 2S-1 in-flight stage inputs,
    so m=64 fits where GPipe+autodiff's O(m+S) boundary storage caps the
    row above at m=16 — the bubble fraction drops (2S-2)/(m+2S-2):
    14/78 = 18% of ticks vs GPipe's 7/23 = 30%.  Same stages, same
    per-microbatch traffic; the comparison against ``gpipe_pp8_dp``
    quantifies what the memory bound buys."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorflowonspark_tpu.parallel import (make_mesh,
                                                make_transformer_stage,
                                                pipeline_value_and_grad,
                                                stack_stage_params)
    from tensorflowonspark_tpu.parallel.mesh import MeshSpec

    mesh = make_mesh(MeshSpec(pp=8, dp=n // 8), devices=jax.devices()[:n])
    hidden, heads, ffn, seq, vocab = 768, 12, 3072, 512, 32768
    num_mb = 64
    batch = num_mb * mesh.shape["dp"]      # 1 sample/mb/shard at m=64
    stage_fn, init_fn, param_specs = make_transformer_stage(
        hidden, heads, ffn, tp=1, causal=True, dtype=jnp.bfloat16)
    tx = optax.adamw(1e-4)
    data_spec = P(("dp", "fsdp"), "sp", None)

    def init_params():
        keys = jax.random.split(jax.random.key(0), 8)
        return {
            "emb": (jax.random.normal(jax.random.key(1), (vocab, hidden))
                    * 0.02).astype(jnp.bfloat16),
            "stages": stack_stage_params([init_fn(k) for k in keys]),
        }

    p_sh = {
        "emb": NamedSharding(mesh, P()),
        "stages": jax.tree.map(
            lambda s: NamedSharding(mesh, P("pp", *s)), param_specs,
            is_leaf=lambda s: isinstance(s, P)),
    }
    abstract_params = jax.eval_shape(init_params)
    abstract_opt = jax.eval_shape(tx.init, abstract_params)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    def head(hp, y, tgt):
        logits = jnp.einsum("bsh,vh->bsv", y, hp["emb"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgt).mean()

    def train_step(p, o, ids):
        x = p["emb"][ids]
        tgt = jnp.roll(ids, -1, axis=1)
        loss, ds, dh, dxe = pipeline_value_and_grad(
            mesh, stage_fn, head, p["stages"], {"emb": p["emb"]},
            x, tgt, num_microbatches=num_mb,
            param_specs=param_specs, data_spec=data_spec,
            target_spec=P(("dp", "fsdp"), None))
        # embedding grad = tied-head grad + the lookup's scatter-add
        demb = dh["emb"] + jnp.zeros_like(p["emb"]).at[ids].add(
            dxe.astype(p["emb"].dtype))
        grads = {"emb": demb, "stages": ds}
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    jitted = jax.jit(
        train_step, donate_argnums=(0, 1),
        in_shardings=(p_sh, None,
                      NamedSharding(mesh, P(("dp", "fsdp"), None))))
    trip = num_mb + 2 * (mesh.shape["pp"] - 1)
    return mesh, jitted, (abstract_params, abstract_opt, ids), trip


WORKLOADS = {"resnet50_dp": _build_resnet_dp,
             "resnet50_dp_2slice": functools.partial(_build_resnet_dp,
                                                     slices=2),
             "bert_tp_sp_dp": _build_bert_gspmd,
             "bert_fsdp8_dp": _build_bert_fsdp,
             "bert_fsdp8_2slice": functools.partial(_build_bert_fsdp,
                                                    slices=2),
             "ring_longctx_sp": _build_ring_longctx,
             "ring_longctx_sp_t8k": functools.partial(_build_ring_longctx,
                                                      per_device_seq=8192),
             "ring16_sp_t8k": functools.partial(_build_sp_attn_h16,
                                                impl="ring"),
             "ulysses16_sp_t8k": functools.partial(_build_sp_attn_h16,
                                                   impl="ulysses"),
             "moe_ep8_dp": _build_moe_ep8,
             "gpipe_pp8_dp": _build_pipeline_pp8,
             "pp8_1f1b_m64_dp": _build_pipeline_pp8_1f1b,
             "gpipe_pp8_2slice": functools.partial(_build_pipeline_pp8,
                                                   slices=2)}

# per-workload size limits (default: every MESH_SIZES entry).  Ulysses
# shards heads over sp, so sp cannot exceed num_heads=16; the ring twin
# runs the same sizes so the comparison stays exact.
WORKLOAD_SIZES = {"ring16_sp_t8k": [8, 16],
                  "ulysses16_sp_t8k": [8, 16]}


def child(workload: str, n: int) -> None:
    import jax

    assert len(jax.devices()) >= n, (len(jax.devices()), n)
    built = WORKLOADS[workload](n)
    mesh, jitted, abstract_args, loop_trip = built[:4]
    dcn_extents = built[4] if len(built) > 4 else None
    # trace under the mesh context, exactly like the dryrun phases: model
    # code gates mesh-dependent sharding anchors (e.g. Bert's act_spec
    # embedding constraint) on a context mesh, and the scaling prediction
    # must price the SAME program the dryrun executes
    with mesh:
        compiled = jitted.lower(*abstract_args).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops_per_device = float(cost.get("flops", 0.0))
    hlo = compiled.as_text()
    comps = _split_computations(hlo)
    mult = _loop_multipliers(comps, loop_trip)
    colls = extract_collectives(hlo, dict(mesh.shape), loop_trip=loop_trip,
                                comps=comps, mult=mult,
                                dcn_extents=dcn_extents)
    loop_flops = _loop_dot_flops(comps, mult)
    print(json.dumps({
        "workload": workload, "n": n, "mesh": dict(mesh.shape),
        "flops_per_device": flops_per_device + loop_flops,
        "flops_cost_analysis": flops_per_device,
        "flops_loop_dot_correction": loop_flops,
        "loop_trip": loop_trip,
        "collectives": colls,
    }))


# ---------------------------------------------------------------------------
# Parent: run children, apply the model, emit the artifact
# ---------------------------------------------------------------------------
def predict(rec: dict) -> dict:
    a = MODEL_ASSUMPTIONS
    mfu = a["mfu"][rec["workload"]]
    t_compute = rec["flops_per_device"] / (a["peak_bf16_flops_per_chip"] * mfu)
    t_comm = 0.0
    per_op = {}
    per_axis_bytes = {}
    for c in rec["collectives"]:
        t = collective_time_s(c["op"], c["bytes"], c["group_size"],
                              dcn=c.get("dcn"))
        t_comm += t
        per_op[c["op"]] = per_op.get(c["op"], 0.0) + t
        key = "x".join(c["axes"]) or "intra"
        if c.get("dcn"):
            key += "(xDCN)"
        per_axis_bytes[key] = per_axis_bytes.get(key, 0.0) + c["bytes"]
    return {
        **rec,
        "t_compute_s": t_compute,
        "t_comm_s": t_comm,
        "t_comm_per_op_s": per_op,
        "bytes_per_axis": per_axis_bytes,
        "efficiency_no_overlap": t_compute / (t_compute + t_comm)
        if t_compute else 0.0,
        "efficiency_full_overlap": t_compute / max(t_compute, t_comm)
        if t_compute else 0.0,
    }


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--child", action="store_true",
                   help="internal: run one (workload, n) compile in this "
                        "process and print its record")
    p.add_argument("--workload", default=None,
                   help="internal, --child only (use --workloads for a "
                        "subset rerun)")
    p.add_argument("--n", type=int, default=None,
                   help="internal, --child only")
    p.add_argument("--sizes", default=",".join(map(str, MESH_SIZES)))
    p.add_argument("--workloads", default=None,
                   help="comma-separated subset to (re)run; their rows "
                        "replace the matching rows of the existing full "
                        "artifact (full sizes only)")
    args = p.parse_args()

    if args.child:
        child(args.workload, args.n)
        return
    if args.workload is not None or args.n is not None:
        raise SystemExit("--workload/--n are child-internal flags; "
                         "did you mean --workloads=<subset>?")

    sizes = [int(v) for v in args.sizes.split(",")]
    selected = list(WORKLOADS) if args.workloads is None else [
        w for w in args.workloads.split(",")]
    for w in selected:
        if w not in WORKLOADS:
            raise SystemExit(f"unknown workload {w!r}; "
                             f"have {sorted(WORKLOADS)}")
    results = []
    for workload in selected:
        for n in [s for s in sizes
                  if s in WORKLOAD_SIZES.get(workload, sizes)]:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={n}")
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--child",
                 "--workload", workload, "--n", str(n)],
                capture_output=True, text=True, env=env, cwd=REPO,
                timeout=1800)
            if proc.returncode != 0:
                print(f"{workload} n={n}: FAILED\n{proc.stderr[-2000:]}",
                      file=sys.stderr)
                continue
            rec = json.loads(proc.stdout.strip().splitlines()[-1])
            # drop the verbose per-op list from the artifact; keep sums
            full = predict(rec)
            full["collectives"] = _summarize(rec["collectives"])
            results.append(full)
            print(f"{workload} n={n}: eff "
                  f"{full['efficiency_no_overlap']:.3f}"
                  f"-{full['efficiency_full_overlap']:.3f} "
                  f"(comm {full['t_comm_s']*1e3:.2f} ms, "
                  f"compute {full['t_compute_s']*1e3:.2f} ms)")

    os.makedirs(os.path.join(REPO, "bench_artifacts"), exist_ok=True)
    # partial sweeps (smoke / debugging) must not clobber the full artifact
    name = "scaling_model.json" if sizes == MESH_SIZES \
        else "scaling_model_partial.json"
    path = os.path.join(REPO, "bench_artifacts", name)
    if args.workloads is not None and sizes == MESH_SIZES \
            and os.path.exists(path):
        # workload-subset rerun: merge per (workload, n) over the existing
        # full artifact — a rerun row replaces its prior same-size row,
        # prior rows survive any sizes the rerun failed at, and a failed
        # rerun can never delete data already in the artifact.  Re-anchor
        # the scaling_* normalization across the merged rows so every
        # workload is consistently normalized to its smallest-n row.
        with open(path) as f:
            prior = json.load(f).get("results", [])
        new_keys = {(r["workload"], r["n"]) for r in results}
        results = [r for r in prior
                   if (r["workload"], r["n"]) not in new_keys] + results
    # normalize efficiencies to the n=8 row (scaling efficiency 8->N) —
    # over the merged list when the merge path ran, else the fresh rows
    _normalize_scaling(results, selected)
    out = {"assumptions": MODEL_ASSUMPTIONS, "results": results}
    # carry the measured-ground-truth section (validate_scaling_model.py)
    # across artifact rewrites; a full rerun changes predictions, so the
    # validation should be re-run too — mark it stale rather than drop it
    try:
        with open(path) as f:
            prior_validation = json.load(f).get("validation")
        if prior_validation:
            # mark each SUBSECTION stale (not the section): a later
            # partial validate run refreshes only the parts it re-ran,
            # so per-part markers are the only ones that stay truthful
            for part in prior_validation.values():
                if isinstance(part, dict):
                    part["stale"] = (
                        "predictions rewritten after this validation "
                        "part ran; re-run "
                        "scripts/validate_scaling_model.py")
            out["validation"] = prior_validation
    except (OSError, ValueError):
        pass
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {path}")


def _normalize_scaling(results: list[dict], workloads) -> None:
    """Anchor each workload's ``scaling_*`` fields to its smallest-n row
    (scaling efficiency 8->N).  Shared by the fresh-sweep and
    merge-into-prior-artifact paths so the two can't drift."""
    for workload in workloads:
        rows = [r for r in results if r["workload"] == workload]
        if not rows:  # every compile for this workload failed
            continue
        base = min(rows, key=lambda r: r["n"])
        for r in rows:
            for key in ("efficiency_no_overlap", "efficiency_full_overlap"):
                r["scaling_" + key] = r[key] / base[key] if base[key] else None


def _summarize(colls: list[dict]) -> dict:
    agg: dict = {}
    for c in colls:
        key = f"{c['op']}@{'x'.join(c['axes']) or 'intra'}"
        a = agg.setdefault(key, {"count": 0, "bytes": 0.0,
                                 "group_size": c["group_size"]})
        a["count"] += 1
        a["bytes"] += c["bytes"]
    return agg


if __name__ == "__main__":
    main()
