"""Cross-language contract linter for the hvt engine (``ci.sh --lint``).

The C++ core and the Python bindings share several hand-maintained
contracts: the ``hvt_*`` C-API symbol list, the append-only
``hvt_engine_stats`` slot ABI, the flight-recorder event kinds, the
control-frame flag bits, and the ``HVT_*`` environment knobs. Each lives
in 3-4 places (``csrc/``, ``engine/native.py``, ``common/basics.py``,
``ci.sh``, ``docs/``); before this linter nothing but reviewer
discipline kept them in sync (the reference pins the same class of
contract with FlatBuffers codegen + a CI sanitizer matrix, SURVEY §5.2).

Six passes, each dependency-free (stdlib ``re``/``ast`` text analysis —
no compiler, no imports of the checked modules):

``capi``
    every ``extern "C"`` function in ``csrc/c_api.cc`` is referenced by
    a binding file and every bound name exists in C. Also the source of
    ``--emit-symbols``, which ci.sh's ``nm -D`` export check consumes
    (the symbol list can no longer be hand-copied and go stale).
``slots``
    ``csrc/stats_slots.h`` is the append-only manifest of the
    ``hvt_engine_stats`` ABI: indices contiguous and unique, names
    matching the layout constants in ``engine/native.py`` slot for
    slot, the count matching the C++ formula (``static_assert`` in
    c_api.cc), and every slot group read by
    ``common/basics.py:poll_engine_stats``.
``events``
    ``csrc/events.h`` EventKind ↔ ``native.EVENT_KINDS`` ↔ the
    ``utils/timeline.py`` drainer mapping (an event kind nobody drains
    is telemetry silently thrown away), plus the wire.h frame-flag
    registry: single-bit values, no collisions per direction (including
    with the 0x80 abort flag), defined once, and actually used.
``env``
    every ``getenv("HVT_…")`` / ``os.environ[...]("HVT_…")`` read in the
    tree has a docs row, and every documented knob still has a read
    site (no ghost documentation).
``codecs``
    the wire-codec registry: codec ids defined once in
    ``csrc/codecs.h`` (``HVT_WIRE_CODECS`` X-macro + the WireCodec
    enum + ``kWireCodecCount``), the Python name table
    (``horovod_tpu/compression`` ``CODEC_IDS`` and ``native.py``
    ``WIRE_CODECS``) and the ``docs/performance.md`` codec table all
    in lockstep — a drifted id would make ranks disagree on transfer
    sizes, a drifted name would mislabel every per-codec metric.
``proto``
    the wire-protocol grammar, extracted statically from
    ``csrc/wire.h`` / ``csrc/transport.h`` (see
    docs/development.md §Protocol grammar): every ``EncodeX`` writes
    the same field sequence its ``DecodeX`` reads; every list
    allocation in a decoder is sized through the bounds-checked
    ``Reader::count`` whose per-element minimum equals the
    grammar-derived minimum encoded size of one element (re-derived
    from the encoder body, so adding a field without updating the
    bound fails lint); no second Reader/Writer definition and no
    cursor-style ``memcpy(&v, …)`` frame reads outside wire.h's
    ``Reader`` (the Reader2 fork this pass exists to prevent); flag
    bytes tested only against the registry names, never hex literals;
    and the Python-side decoders (``elastic/state.py`` shard frames,
    the kvbulk envelopes between ``metrics/telemetry.py`` and
    ``runner/http_server.py``) matching their documented framing.

Run ``python -m horovod_tpu.tools.hvt_lint`` (all passes), optionally
naming a subset, ``--root`` for an alternate tree (the fixture tests
use it), or ``--emit-symbols`` to print the canonical C-API symbol
list. Exit status 0 = clean, 1 = violations, 2 = usage/parse errors.
"""

from __future__ import annotations

import argparse
import ast
import re
import sys
from pathlib import Path

# ---------------------------------------------------------------------------
# tree layout — relative to the repo root. tests/test_hvt_lint.py builds
# fixture trees with these same paths, so keep them data, not code.
# ---------------------------------------------------------------------------
C_API_CC = "horovod_tpu/csrc/c_api.cc"
CODECS_H = "horovod_tpu/csrc/codecs.h"
COMPRESSION_PY = "horovod_tpu/compression/__init__.py"
PERFORMANCE_MD = "docs/performance.md"
ENGINE_H = "horovod_tpu/csrc/engine.h"
ENGINE_CC = "horovod_tpu/csrc/engine.cc"
EVENTS_H = "horovod_tpu/csrc/events.h"
WIRE_H = "horovod_tpu/csrc/wire.h"
TRANSPORT_H = "horovod_tpu/csrc/transport.h"
STATE_PY = "horovod_tpu/elastic/state.py"
TELEMETRY_PY = "horovod_tpu/metrics/telemetry.py"
HTTP_SERVER_PY = "horovod_tpu/runner/http_server.py"
STATS_SLOTS_H = "horovod_tpu/csrc/stats_slots.h"
NATIVE_PY = "horovod_tpu/engine/native.py"
BASICS_PY = "horovod_tpu/common/basics.py"
TIMELINE_PY = "horovod_tpu/utils/timeline.py"
CSRC_DIR = "horovod_tpu/csrc"
DOCS_DIR = "docs"

# Files allowed (and required) to bind hvt_* symbols over ctypes. The
# first is the production bridge; the test files bind the test-only
# entry points (GP/BO internals, ScaleBuffer, autotune state).
BINDING_FILES = (
    NATIVE_PY,
    "tests/test_autotune.py",
    "tests/test_ring_kernels.py",
)

# Where HVT_* env reads count as product surface needing documentation.
# tests/ and examples/ set knobs but their reads are not user surface.
ENV_SCAN_DIRS = ("horovod_tpu", "benchmarks")

# The four per-op slot groups and the two engine histograms, in the
# exact order hvt_engine_stats emits them (after the scalar block,
# before the abort-cause block).
SLOT_OP_GROUPS = ("exec_ns", "exec_count", "wire_tx_bytes",
                  "wire_tx_comp_bytes")
SLOT_HISTS = ("cycle_hist", "wakeup_hist")
# Per-set lane telemetry appended after the abort causes: a
# "lanes_active" scalar, then these groups with STATS_LANE_SLOTS
# (native.py) == kLaneSlots (engine.h) entries each. Optional — a tree
# without lane slots (the fixture mini-trees) simply omits the
# constants on BOTH sides.
SLOT_LANE_GROUPS = ("lane_depth", "lane_exec_ns", "lane_exec_count")
# Plain scalar slots appended LAST (after the lane block): native.py
# names them in STATS_TAIL_SCALARS and c_api.cc sizes them with
# kStatsTailScalars — the append-only escape hatch for new counters
# that fit no structured group. Optional on the same both-sides terms
# as the lane block.


def _read(root: Path, rel: str, vios: list, pass_name: str):
    p = root / rel
    try:
        return p.read_text()
    except OSError:
        vios.append(f"{pass_name}: {rel}: file missing (the {pass_name} "
                    f"pass cannot run without it)")
        return None


def _py_literals(text: str, names: set):
    """Top-level ``NAME = <literal>`` assignments from a module's source
    (ast.literal_eval — no import, so jax/numpy never load)."""
    out = {}
    try:
        tree = ast.parse(text)
    except SyntaxError:
        return out
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if isinstance(tgt, ast.Name) and tgt.id in names:
            try:
                out[tgt.id] = ast.literal_eval(node.value)
            except ValueError:
                pass
    return out


def _c_int_const(text: str, name: str):
    m = re.search(rf'constexpr\s+int\s+{name}\s*=\s*(\d+)\s*;', text)
    return int(m.group(1)) if m else None


# ---------------------------------------------------------------------------
# pass 1: C-API parity
# ---------------------------------------------------------------------------

# Any non-static file-scope definition/declaration `<type tokens>
# hvt_name(` — deliberately loose on the return type (int, void,
# long long, const char*, int64_t, …) so a new entry point can never
# dodge the parity check by returning a type the regex never met.
# Call sites don't match: they are indented (the anchor is column 0).
_C_DEF_RE = re.compile(
    r'^(?!static\b)(?:[A-Za-z_][A-Za-z0-9_:<>]*[ \t*]+)+(hvt_\w+)\s*\(',
    re.M)
# ctypes references: `lib.hvt_x` / `_lib.hvt_x(...)` / `lib().hvt_x`,
# plus the getattr probe used for graceful degradation on stale .so's.
_PY_ATTR_RE = re.compile(r'\.\s*(hvt_\w+)\b')
_PY_GETATTR_RE = re.compile(r'getattr\(\s*\w+\s*,\s*"(hvt_\w+)"')


def c_api_symbols(root: Path):
    """The extern-C surface of c_api.cc (sorted). Raises on a missing
    file — callers that want a violation instead use check_capi."""
    text = (root / C_API_CC).read_text()
    return sorted(set(_C_DEF_RE.findall(text)))


def check_capi(root: Path):
    vios = []
    text = _read(root, C_API_CC, vios, "capi")
    if text is None:
        return vios
    defs = _C_DEF_RE.findall(text)
    dup = {s for s in defs if defs.count(s) > 1}
    for s in sorted(dup):
        vios.append(f"capi: {C_API_CC}: symbol {s} defined more than once")
    syms = set(defs)
    if 'extern "C"' not in text:
        vios.append(f'capi: {C_API_CC}: no extern "C" block — every '
                    f'hvt_* entry point must have C linkage for ctypes')
    refs = {}  # symbol -> first referencing file
    for rel in BINDING_FILES:
        p = root / rel
        if not p.exists():
            # test-binding files are optional in fixture trees; the
            # production bridge is not
            if rel == NATIVE_PY:
                vios.append(f"capi: {rel}: file missing (the ctypes "
                            f"bridge is the binding side of the parity "
                            f"check)")
            continue
        body = p.read_text()
        for sym in (_PY_ATTR_RE.findall(body)
                    + _PY_GETATTR_RE.findall(body)):
            refs.setdefault(sym, rel)
    for sym in sorted(syms - set(refs)):
        vios.append(
            f"capi: {C_API_CC}: {sym} is exported but bound nowhere in "
            f"{', '.join(BINDING_FILES)} — dead C API surface (bind it "
            f"or remove it)")
    for sym, rel in sorted(refs.items()):
        if sym not in syms:
            vios.append(
                f"capi: {rel}: binds {sym}, which c_api.cc does not "
                f"define — the call will fail at runtime on attribute "
                f"lookup")
    return vios


# ---------------------------------------------------------------------------
# pass 2: stats-slot ABI manifest
# ---------------------------------------------------------------------------

_SLOT_RE = re.compile(r'X\(\s*(\d+)\s*,\s*"([^"]+)"\s*\)')
_SLOT_COUNT_RE = re.compile(r'#define\s+HVT_STATS_SLOT_COUNT\s+(\d+)')


def check_slots(root: Path):
    vios = []
    manifest = _read(root, STATS_SLOTS_H, vios, "slots")
    native = _read(root, NATIVE_PY, vios, "slots")
    engine_h = _read(root, ENGINE_H, vios, "slots")
    c_api = _read(root, C_API_CC, vios, "slots")
    basics = _read(root, BASICS_PY, vios, "slots")
    if None in (manifest, native, engine_h, c_api, basics):
        return vios

    slots = [(int(i), n) for i, n in _SLOT_RE.findall(manifest)]
    m = _SLOT_COUNT_RE.search(manifest)
    declared = int(m.group(1)) if m else None
    if declared is None:
        vios.append(f"slots: {STATS_SLOTS_H}: no "
                    f"#define HVT_STATS_SLOT_COUNT")
    elif declared != len(slots):
        vios.append(
            f"slots: {STATS_SLOTS_H}: HVT_STATS_SLOT_COUNT is "
            f"{declared} but the manifest lists {len(slots)} slots")

    # append-only structure: indices must be 0..n-1 in order, no reuse
    seen = {}
    for pos, (idx, name) in enumerate(slots):
        if idx in seen:
            vios.append(
                f"slots: {STATS_SLOTS_H}: slot index {idx} is used by "
                f"both \"{seen[idx]}\" and \"{name}\" — slot indices "
                f"are an append-only ABI and may never be reused")
        seen[idx] = name
        if idx != pos:
            vios.append(
                f"slots: {STATS_SLOTS_H}: slot \"{name}\" has index "
                f"{idx} at manifest position {pos} — indices must be "
                f"contiguous from 0 (append new slots at the end; "
                f"never renumber)")
    names = [n for _, n in slots]
    for n in sorted({x for x in names if names.count(x) > 1}):
        vios.append(f"slots: {STATS_SLOTS_H}: slot name \"{n}\" appears "
                    f"more than once")

    # Python layout parity: rebuild the expected slot list from the
    # constants the ctypes decoder actually uses.
    consts = _py_literals(native, {"STATS_SCALARS", "STATS_OPS",
                                   "STATS_LAT_BUCKETS", "ABORT_CAUSES",
                                   "STATS_LANE_SLOTS",
                                   "STATS_TAIL_SCALARS", "WIRE_CODECS",
                                   "STATS_EF_SCALARS",
                                   "STATS_LINK_PLANES",
                                   "STATS_RECOVERY_SCALARS",
                                   "STATS_LANE_POOL_SCALARS",
                                   "STATS_LANE_HOL_GROUPS",
                                   "STATS_URING_SCALARS"})
    missing = [k for k in ("STATS_SCALARS", "STATS_OPS",
                           "STATS_LAT_BUCKETS", "ABORT_CAUSES")
               if k not in consts]
    if missing:
        vios.append(f"slots: {NATIVE_PY}: layout constants "
                    f"{missing} not found as literal assignments")
        return vios
    lane_slots = int(consts.get("STATS_LANE_SLOTS", 0) or 0)
    tail = list(consts.get("STATS_TAIL_SCALARS", ()) or ())
    # per-codec byte block + EF scalars (appended after the tail
    # scalars) — optional on the same both-sides terms as the lane
    # block (fixture mini-trees predate the codec registry)
    codecs = list(consts.get("WIRE_CODECS", ()) or ())
    ef = list(consts.get("STATS_EF_SCALARS", ()) or ())
    # self-healing link block (appended after the EF scalars) —
    # optional on the same both-sides terms as the codec block
    planes = list(consts.get("STATS_LINK_PLANES", ()) or ())
    recovery = list(consts.get("STATS_RECOVERY_SCALARS", ()) or ())
    # per-lane execution pool block (appended after the recovery
    # scalars) — optional on the same both-sides terms as the others
    lane_pool = list(consts.get("STATS_LANE_POOL_SCALARS", ()) or ())
    # per-lane head-of-line block (appended after the pool scalars) —
    # optional on the same both-sides terms as the others
    lane_hol = list(consts.get("STATS_LANE_HOL_GROUPS", ()) or ())
    # transport-backend block (appended after the head-of-line groups)
    # — optional on the same both-sides terms as the others
    uring = list(consts.get("STATS_URING_SCALARS", ()) or ())
    expected = list(consts["STATS_SCALARS"])
    for grp in SLOT_OP_GROUPS:
        expected += [f"{grp}[{op}]" for op in consts["STATS_OPS"]]
    for h in SLOT_HISTS:
        expected += [f"{h}.bucket[{i}]"
                     for i in range(consts["STATS_LAT_BUCKETS"] + 1)]
        expected += [f"{h}.sum_ns", f"{h}.count"]
    expected += [f"aborts[{c}]" for c in consts["ABORT_CAUSES"]]
    if lane_slots:
        expected += ["lanes_active"]
        for grp in SLOT_LANE_GROUPS:
            expected += [f"{grp}[{i}]" for i in range(lane_slots)]
    expected += tail
    for codec in codecs:
        expected += [f"codec_tx_bytes[{codec}][{op}]"
                     for op in consts["STATS_OPS"]]
    expected += ef
    expected += [f"link_reconnects[{p}]" for p in planes]
    expected += recovery
    expected += lane_pool
    for grp in lane_hol:
        expected += [f"{grp}[{i}]" for i in range(lane_slots)]
    expected += uring
    if names != expected:
        diffs = [i for i, (a, b) in enumerate(zip(names, expected))
                 if a != b]
        where = (f"first mismatch at slot {diffs[0]}: manifest "
                 f"\"{names[diffs[0]]}\" vs python layout "
                 f"\"{expected[diffs[0]]}\"" if diffs else
                 f"manifest has {len(names)} slots, python layout "
                 f"implies {len(expected)}")
        vios.append(f"slots: {STATS_SLOTS_H}: manifest does not match "
                    f"the {NATIVE_PY} layout constants ({where})")

    # C++ side: the formula must reproduce the manifest count, and
    # c_api.cc must pin it with a static_assert against the manifest.
    ops = _c_int_const(engine_h, "kStatsOps")
    lat = _c_int_const(engine_h, "kLatBuckets")
    causes = _c_int_const(engine_h, "kAbortCauses")
    scalars = _c_int_const(c_api, "kStatsScalars")
    c_lanes = _c_int_const(engine_h, "kLaneSlots") or 0
    c_tail = _c_int_const(c_api, "kStatsTailScalars") or 0
    codecs_h = (root / CODECS_H).read_text() \
        if (root / CODECS_H).exists() else ""
    c_codecs = _c_int_const(codecs_h, "kWireCodecCount") or 0
    c_ef = _c_int_const(c_api, "kStatsEfScalars") or 0
    c_planes = _c_int_const(c_api, "kStatsLinkPlanes") or 0
    c_recovery = _c_int_const(c_api, "kStatsRecoveryScalars") or 0
    c_lane_pool = _c_int_const(c_api, "kStatsLanePoolScalars") or 0
    if c_lane_pool != len(lane_pool):
        vios.append(
            f"slots: {C_API_CC} kStatsLanePoolScalars={c_lane_pool} but "
            f"{NATIVE_PY} STATS_LANE_POOL_SCALARS has {len(lane_pool)} "
            f"entries — the lane-pool scalar block would decode shifted")
    c_lane_hol = _c_int_const(c_api, "kStatsLaneHolGroups") or 0
    if c_lane_hol != len(lane_hol):
        vios.append(
            f"slots: {C_API_CC} kStatsLaneHolGroups={c_lane_hol} but "
            f"{NATIVE_PY} STATS_LANE_HOL_GROUPS has {len(lane_hol)} "
            f"entries — the head-of-line block would decode shifted")
    c_uring = _c_int_const(c_api, "kStatsUringScalars") or 0
    if c_uring != len(uring):
        vios.append(
            f"slots: {C_API_CC} kStatsUringScalars={c_uring} but "
            f"{NATIVE_PY} STATS_URING_SCALARS has {len(uring)} "
            f"entries — the transport-backend block would decode "
            f"shifted")
    if c_planes != len(planes):
        vios.append(
            f"slots: {C_API_CC} kStatsLinkPlanes={c_planes} but "
            f"{NATIVE_PY} STATS_LINK_PLANES has {len(planes)} entries — "
            f"the link-reconnect block would decode shifted")
    if c_recovery != len(recovery):
        vios.append(
            f"slots: {C_API_CC} kStatsRecoveryScalars={c_recovery} but "
            f"{NATIVE_PY} STATS_RECOVERY_SCALARS has {len(recovery)} "
            f"entries — the replay scalar block would decode shifted")
    if c_codecs != len(codecs):
        vios.append(
            f"slots: {CODECS_H} kWireCodecCount={c_codecs} but "
            f"{NATIVE_PY} WIRE_CODECS has {len(codecs)} entries — the "
            f"per-codec byte block would decode shifted")
    if c_ef != len(ef):
        vios.append(
            f"slots: {C_API_CC} kStatsEfScalars={c_ef} but {NATIVE_PY} "
            f"STATS_EF_SCALARS has {len(ef)} entries — the EF scalar "
            f"block would decode shifted")
    if c_lanes != lane_slots:
        vios.append(
            f"slots: {ENGINE_H} kLaneSlots={c_lanes} but {NATIVE_PY} "
            f"STATS_LANE_SLOTS={lane_slots} — the lane-telemetry blocks "
            f"would decode shifted")
    if c_tail != len(tail):
        vios.append(
            f"slots: {C_API_CC} kStatsTailScalars={c_tail} but "
            f"{NATIVE_PY} STATS_TAIL_SCALARS has {len(tail)} entries — "
            f"the trailing scalar block would decode shifted")
    if None in (ops, lat, causes, scalars):
        vios.append(
            f"slots: could not parse kStatsOps/kLatBuckets/kAbortCauses "
            f"({ENGINE_H}) and kStatsScalars ({C_API_CC})")
    else:
        c_count = (scalars + len(SLOT_OP_GROUPS) * ops
                   + len(SLOT_HISTS) * (lat + 1 + 2) + causes
                   + (1 + len(SLOT_LANE_GROUPS) * c_lanes
                      if c_lanes else 0) + c_tail
                   + c_codecs * ops + c_ef + c_planes + c_recovery
                   + c_lane_pool + c_lane_hol * c_lanes + c_uring)
        if declared is not None and c_count != declared:
            vios.append(
                f"slots: {C_API_CC}: C++ layout emits {c_count} slots "
                f"but HVT_STATS_SLOT_COUNT is {declared} — append the "
                f"new slots to {STATS_SLOTS_H} (never renumber)")
        if scalars != len(consts["STATS_SCALARS"]):
            vios.append(
                f"slots: {C_API_CC}: kStatsScalars={scalars} but "
                f"{NATIVE_PY} STATS_SCALARS has "
                f"{len(consts['STATS_SCALARS'])} entries")
    if "stats_slots.h" not in c_api or \
            not re.search(r'static_assert[^;]*HVT_STATS_SLOT_COUNT',
                          c_api, re.S):
        vios.append(
            f"slots: {C_API_CC}: must #include \"stats_slots.h\" and "
            f"static_assert its emitted slot count against "
            f"HVT_STATS_SLOT_COUNT so the C side cannot drift silently")

    # metrics bridge coverage: every slot group the manifest lists must
    # be consumed by poll_engine_stats (a slot nobody reads is telemetry
    # silently thrown away).
    claimed = list(consts["STATS_SCALARS"]) + list(SLOT_OP_GROUPS) + \
        list(SLOT_HISTS) + ["aborts"]
    if lane_slots:
        claimed += ["lanes_active"] + list(SLOT_LANE_GROUPS)
    claimed += tail
    if codecs:
        claimed += ["codec_tx_bytes"]
    claimed += ef
    if planes:
        claimed += ["link_reconnects"]
    claimed += recovery
    claimed += lane_pool
    claimed += lane_hol
    claimed += uring
    for key in claimed:
        if f'"{key}"' not in basics:
            vios.append(
                f"slots: {BASICS_PY}: poll_engine_stats never reads "
                f"\"{key}\" — every manifest slot group must reach the "
                f"metrics plane")
    return vios


# ---------------------------------------------------------------------------
# pass 3: event-kind and wire-flag parity
# ---------------------------------------------------------------------------

_ENUM_RE = re.compile(r'enum\s+class\s+EventKind[^{]*\{(.*?)\};', re.S)
_ENUM_ENTRY_RE = re.compile(r'^\s*(\w+)\s*=\s*(\d+)\s*,?', re.M)
_FLAG_RE = re.compile(
    r'constexpr\s+uint8_t\s+(k\w*Flag\w*)\s*=\s*(0x[0-9A-Fa-f]+|\d+)\s*;')
# control-plane role registry (hierarchical negotiation): engine.h
# CtrlRole wire ids are stamped into CTRL_BYTES events and decoded by
# the timeline drainer through CTRL_ROLES — both sides optional (the
# fixture mini-trees predate the tree control plane), but when either
# exists the other must match name-for-name.
_CTRL_ROLE_RE = re.compile(r'enum\s+class\s+CtrlRole[^{]*\{(.*?)\};',
                           re.S)


def _timeline_kind_locals(text: str):
    """The positional `_ENQUEUED, ... = range(N)` unpack in timeline.py:
    returns (names, N, use_counts) or None."""
    try:
        tree = ast.parse(text)
    except SyntaxError:
        return None
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Tuple)):
            continue
        elts = node.targets[0].elts
        if not elts or not all(isinstance(e, ast.Name)
                               and e.id.startswith("_") for e in elts):
            continue
        v = node.value
        if not (isinstance(v, ast.Call) and isinstance(v.func, ast.Name)
                and v.func.id == "range" and len(v.args) == 1
                and isinstance(v.args[0], ast.Constant)):
            continue
        names = [e.id for e in elts]
        uses = {n: 0 for n in names}
        for n2 in ast.walk(tree):
            if isinstance(n2, ast.Name) and n2.id in uses and \
                    isinstance(n2.ctx, ast.Load):
                uses[n2.id] += 1
        return names, int(v.args[0].value), uses
    return None


def check_events(root: Path):
    vios = []
    events_h = _read(root, EVENTS_H, vios, "events")
    native = _read(root, NATIVE_PY, vios, "events")
    timeline = _read(root, TIMELINE_PY, vios, "events")
    wire_h = _read(root, WIRE_H, vios, "events")
    if None in (events_h, native, timeline, wire_h):
        return vios

    # control-plane role registry: engine.h CtrlRole ↔ timeline.py
    # CTRL_ROLES (index == wire id). Optional on both-sides terms like
    # the lane-slot block; a one-sided presence or a name/order drift
    # would mislabel every CTRL instant's role attribution.
    engine_h = (root / ENGINE_H).read_text() \
        if (root / ENGINE_H).exists() else ""
    role_m = _CTRL_ROLE_RE.search(engine_h)
    py_roles = list(_py_literals(timeline, {"CTRL_ROLES"})
                    .get("CTRL_ROLES", ()))
    if role_m or py_roles:
        c_roles = []
        for name, val in _ENUM_ENTRY_RE.findall(
                role_m.group(1) if role_m else ""):
            if int(val) != len(c_roles):
                vios.append(
                    f"events: {ENGINE_H}: CtrlRole::{name} = {val} — "
                    f"role wire ids must stay contiguous from 0 (they "
                    f"index the CTRL_ROLES table)")
            c_roles.append(name.lower())
        if not role_m:
            vios.append(
                f"events: {TIMELINE_PY}: CTRL_ROLES is defined but "
                f"{ENGINE_H} has no enum class CtrlRole — the role "
                f"registry must live on both sides")
        elif c_roles != py_roles:
            vios.append(
                f"events: {TIMELINE_PY}: CTRL_ROLES {py_roles} does not "
                f"match {ENGINE_H} CtrlRole {c_roles} — CTRL instants "
                f"would attribute control bytes to the wrong role")

    m = _ENUM_RE.search(events_h)
    if not m:
        vios.append(f"events: {EVENTS_H}: enum class EventKind not found")
        return vios
    entries = [(name, int(val))
               for name, val in _ENUM_ENTRY_RE.findall(m.group(1))]
    kinds = [name for name, _ in entries]
    for pos, (name, val) in enumerate(entries):
        if val != pos:
            vios.append(
                f"events: {EVENTS_H}: EventKind::{name} = {val} at "
                f"position {pos} — wire ids are append-only and must "
                f"stay contiguous from 0")

    consts = _py_literals(native, {"EVENT_KINDS"})
    ek = list(consts.get("EVENT_KINDS", ()))
    if not ek:
        vios.append(f"events: {NATIVE_PY}: EVENT_KINDS tuple not found")
    elif ek != kinds:
        vios.append(
            f"events: {NATIVE_PY}: EVENT_KINDS {ek} does not match "
            f"{EVENTS_H} EventKind {kinds} — the index-is-wire-id "
            f"mapping would mislabel drained events")

    # drainer coverage: the timeline's positional kind ids must cover
    # every kind, and each must be referenced by the converter.
    tl = _timeline_kind_locals(timeline)
    if tl is None:
        vios.append(f"events: {TIMELINE_PY}: positional kind-id unpack "
                    f"(`_ENQUEUED, ... = range(N)`) not found")
    else:
        names, n, uses = tl
        if n != len(kinds) or len(names) != len(kinds):
            vios.append(
                f"events: {TIMELINE_PY}: drainer knows {len(names)} "
                f"kind ids (range({n})) but {EVENTS_H} defines "
                f"{len(kinds)} — new kinds must be mapped onto timeline "
                f"lanes (or explicitly skipped) in the drainer")
        for pos, local in enumerate(names):
            if uses.get(local, 0) == 0:
                kind = kinds[pos] if pos < len(kinds) else f"#{pos}"
                vios.append(
                    f"events: {TIMELINE_PY}: kind {kind} ({local}) is "
                    f"never referenced by the drainer — its events are "
                    f"recorded by the engine and then silently dropped")

    # wire-flag registry
    flags = [(name, int(val, 0)) for name, val in _FLAG_RE.findall(wire_h)]
    flag_names = [n for n, _ in flags]
    for name, val in flags:
        if val == 0 or (val & (val - 1)) != 0 or val > 0xFF:
            vios.append(
                f"events: {WIRE_H}: {name} = {val:#x} is not a single "
                f"uint8 bit — frame flags are OR-combined and must each "
                f"own one bit")
    abort = dict(flags).get("kAbortFrameFlag")
    if abort is None:
        vios.append(f"events: {WIRE_H}: kAbortFrameFlag is not "
                    f"registered (the abort bit must live in the "
                    f"registry like every other flag)")
    for prefix, direction in (("kCtrlFlag", "worker→rank-0"),
                              ("kRespFlag", "rank-0→worker")):
        group = [(n, v) for n, v in flags if n.startswith(prefix)]
        if abort is not None:
            group.append(("kAbortFrameFlag", abort))
        used = {}
        for n, v in group:
            if v in used:
                vios.append(
                    f"events: {WIRE_H}: {n} and {used[v]} both claim "
                    f"bit {v:#x} in the {direction} frame byte")
            used[v] = n
    # defined once, and actually used: the registry is the ONLY home of
    # flag constants, and a registered flag nobody reads is stale.
    csrc = root / CSRC_DIR
    other = [p for p in csrc.glob("*.cc")] + \
        [p for p in csrc.glob("*.h") if p.name != Path(WIRE_H).name]
    bodies = {p: p.read_text() for p in other if p.exists()}
    for name, _ in flags:
        if any(re.search(rf'constexpr[^;\n]*\b{name}\s*=', b)
               for b in bodies.values()):
            culprit = [p.name for p, b in bodies.items()
                       if re.search(rf'constexpr[^;\n]*\b{name}\s*=', b)]
            vios.append(
                f"events: {culprit[0]}: re-defines {name} — frame-flag "
                f"bits are registered exactly once, in {WIRE_H}")
        # a use site is a reference outside the defining declaration —
        # in any other csrc file, or in wire.h's own inline codecs
        # (e.g. the bitmask announce encoder lives beside the registry).
        # Comments are stripped so a doc mention can't masquerade as use.
        wire_code = re.sub(r'//[^\n]*', '', wire_h)
        wire_uses = len(re.findall(rf'\b{name}\b', wire_code)) \
            - len(re.findall(rf'constexpr[^;\n]*\b{name}\s*=', wire_code))
        if wire_uses <= 0 and \
                not any(re.search(rf'\b{name}\b', b)
                        for b in bodies.values()):
            vios.append(
                f"events: {WIRE_H}: {name} is registered but never used "
                f"by the engine — remove it or wire it up")
    return vios


# ---------------------------------------------------------------------------
# pass 4: env-var documentation coverage
# ---------------------------------------------------------------------------

_PY_ENV_RE = re.compile(
    r'(?:environ\.get\(\s*|environ\[\s*|getenv\(\s*)"(HVT_[A-Z0-9_]+)"')
_C_ENV_RE = re.compile(r'(?:getenv|EnvInt)\(\s*"(HVT_[A-Z0-9_]+)"')
_DOC_TOKEN_RE = re.compile(r'\bHVT_[A-Z0-9_]+\b')
# HVT_-prefixed C macros the docs legitimately mention — not env knobs.
_NOT_ENV_VARS = {"HVT_STATS_SLOT_COUNT", "HVT_STATS_SLOTS", "HVT_LOG",
                 "HVT_THREAD_ANNOTATION__"}


def _env_read_sites(root: Path):
    reads = {}  # var -> first "path" seen

    def scan(path: Path, rel: str):
        if path.suffix == ".py":
            env_re = _PY_ENV_RE
        elif path.suffix in (".cc", ".h"):
            env_re = _C_ENV_RE
        else:
            return
        try:
            text = path.read_text(errors="replace")
        except OSError:
            return
        for var in env_re.findall(text):
            reads.setdefault(var, rel)

    for d in ENV_SCAN_DIRS:
        base = root / d
        if not base.is_dir():
            continue
        for p in sorted(base.rglob("*")):
            if p.is_file():
                scan(p, str(p.relative_to(root)))
    return reads


def check_env(root: Path):
    vios = []
    docs = sorted((root / DOCS_DIR).glob("*.md")) \
        if (root / DOCS_DIR).is_dir() else []
    if not docs:
        vios.append(f"env: {DOCS_DIR}/: no markdown docs found")
        return vios
    documented = {}  # var -> first doc file
    for p in docs:
        rel = str(p.relative_to(root))
        for var in _DOC_TOKEN_RE.findall(p.read_text()):
            if var not in _NOT_ENV_VARS:
                documented.setdefault(var, rel)
    reads = _env_read_sites(root)
    for var, rel in sorted(reads.items()):
        if var not in documented:
            vios.append(
                f"env: {rel}: reads {var}, which is documented nowhere "
                f"under {DOCS_DIR}/ — every knob needs a docs row "
                f"(docs/development.md explains where each family "
                f"belongs)")
    for var, rel in sorted(documented.items()):
        if var not in reads:
            vios.append(
                f"env: {rel}: documents {var}, but no code reads it — "
                f"delete the stale row (or restore the read site)")
    return vios


# ---------------------------------------------------------------------------
# pass 5: wire-codec registry parity
# ---------------------------------------------------------------------------

_CODEC_ENUM_RE = re.compile(r'enum\s+class\s+WireCodec[^{]*\{(.*?)\};',
                            re.S)


def _doc_codec_table(perf_md: str):
    """Backticked first-column names of the docs codec table (the
    markdown table following the 'codec table' heading); None when the
    heading is absent."""
    m = re.search(r'^#+.*codec table.*$', perf_md, re.M | re.I)
    if not m:
        return None
    names = []
    for line in perf_md[m.end():].splitlines():
        line = line.strip()
        if names and not line.startswith("|"):
            break
        row = re.match(r'\|\s*`([^`]+)`\s*\|', line)
        if row:
            names.append(row.group(1))
    return names


def check_codecs(root: Path):
    vios = []
    have_h = (root / CODECS_H).exists()
    have_py = (root / COMPRESSION_PY).exists()
    if not have_h and not have_py:
        return vios  # pre-codec-registry tree (fixture mini-trees)
    codecs_h = _read(root, CODECS_H, vios, "codecs")
    comp_py = _read(root, COMPRESSION_PY, vios, "codecs")
    native = _read(root, NATIVE_PY, vios, "codecs")
    perf_md = _read(root, PERFORMANCE_MD, vios, "codecs")
    if None in (codecs_h, comp_py, native, perf_md):
        return vios

    # registry X-macro: ids contiguous from 0, names unique
    rows = [(int(i), n) for i, n in _SLOT_RE.findall(codecs_h)]
    names = [n for _, n in rows]
    for pos, (idx, name) in enumerate(rows):
        if idx != pos:
            vios.append(
                f"codecs: {CODECS_H}: codec \"{name}\" has id {idx} at "
                f"registry position {pos} — codec ids are wire values "
                f"and must stay contiguous from 0 (append, never "
                f"renumber)")
    count = _c_int_const(codecs_h, "kWireCodecCount")
    if count != len(rows):
        vios.append(
            f"codecs: {CODECS_H}: kWireCodecCount={count} but the "
            f"HVT_WIRE_CODECS registry lists {len(rows)} codecs")
    # the enum must cover exactly the registry ids
    em = _CODEC_ENUM_RE.search(codecs_h)
    if not em:
        vios.append(f"codecs: {CODECS_H}: enum class WireCodec not found")
    else:
        entries = [(n, int(v))
                   for n, v in _ENUM_ENTRY_RE.findall(em.group(1))]
        if sorted(v for _, v in entries) != list(range(len(rows))):
            vios.append(
                f"codecs: {CODECS_H}: WireCodec enum ids "
                f"{sorted(v for _, v in entries)} do not cover the "
                f"registry ids 0..{len(rows) - 1} — enum and registry "
                f"must describe the same wire values")

    # python name tables: compression.CODEC_IDS and native.WIRE_CODECS
    ids = _py_literals(comp_py, {"CODEC_IDS"}).get("CODEC_IDS")
    if not isinstance(ids, dict):
        vios.append(f"codecs: {COMPRESSION_PY}: CODEC_IDS dict literal "
                    f"not found")
    elif ids != {n: i for i, n in enumerate(names)}:
        vios.append(
            f"codecs: {COMPRESSION_PY}: CODEC_IDS {ids} does not match "
            f"the {CODECS_H} registry "
            f"{{{', '.join(f'{n!r}: {i}' for i, n in enumerate(names))}}}"
            f" — the Python name table would mislabel wire ids")
    wire_codecs = list(_py_literals(native, {"WIRE_CODECS"})
                       .get("WIRE_CODECS", ()) or ())
    if wire_codecs != names:
        vios.append(
            f"codecs: {NATIVE_PY}: WIRE_CODECS {wire_codecs} does not "
            f"match the {CODECS_H} registry {names} — per-codec stats "
            f"would decode under the wrong labels")

    # docs codec table: one row per registry codec, no stale rows
    doc = _doc_codec_table(perf_md)
    if doc is None:
        vios.append(
            f"codecs: {PERFORMANCE_MD}: no 'codec table' heading — the "
            f"codec guide must table every registry codec")
    elif sorted(doc) != sorted(names):
        vios.append(
            f"codecs: {PERFORMANCE_MD}: codec table rows {sorted(doc)} "
            f"do not match the {CODECS_H} registry {sorted(names)} — "
            f"add the missing row / delete the stale one")
    return vios


# ---------------------------------------------------------------------------
# pass 6: wire-protocol grammar (hvt_proto)
# ---------------------------------------------------------------------------
# Extracts the frame grammar from the Encode*/Decode* bodies in wire.h
# (docs/development.md §Protocol grammar) and checks, without compiling
# anything:
#   * encoder↔decoder field symmetry per pair,
#   * count()-routed allocations with a per-element minimum that equals
#     the minimum encoded element size RE-DERIVED from the encoder,
#   * the Reader containment boundary (no Reader/Writer forks, no
#     cursor-style memcpy reads outside wire.h's Reader),
#   * flag-byte tests only against the registry names, and
#   * the Python-side framing contracts (state shards, kvbulk).

# bytes contributed by one writer/reader primitive when the frame is
# minimal (every variable-length field empty): str/i64vec cost their
# 4-byte length prefix
_WIRE_TOK_BYTES = {"u8": 1, "i32": 4, "i64": 8, "f64": 8,
                   "str": 4, "i64vec": 4}

_PROTO_FN_RE = re.compile(
    r'\binline\s+[^;{}()]*?\b((?:Encode|Decode)\w+)\s*\(')
_ENC_TOK_RE = re.compile(
    r'\bw\.(u8|i32|i64|f64|str|i64vec)\s*\('
    r'|\bEncode(\w+)\s*\(\s*w\s*,')
_DEC_TOK_RE = re.compile(
    r'\brd\.(u8|i32|i64|f64|str|i64vec|count)\s*\('
    r'|\bDecode(\w+)\s*\(\s*rd\b')
_COUNT_ASSIGN_RE = re.compile(
    r'(\w+)\s*=\s*rd\.count\(([^()]*(?:\([^()]*\)[^()]*)*)\)')
_RESIZE_RE = re.compile(r'[\w\].]+\.resize\(\s*([^()]+?)\s*\)')
_VEC_ALLOC_RE = re.compile(r'\bstd::vector<[^<>]*(?:<[^<>]*>)?[^<>]*>\s+'
                           r'(\w+)\s*\(\s*(\w+)\s*\)')
_READER_FORK_RE = re.compile(r'\b(?:struct|class)\s+((?:Reader|Writer)\w*)'
                             r'\s*(?::[^{;]*)?\{')
_FLAG_LITERAL_RE = re.compile(
    r'\b(?:first|flags|resp_flags|frame\[0\]|f\[0\])\s*[&|]\s*'
    r'(?:~\s*)?(0x[0-9A-Fa-f]+|\d+)\b')
_PROTO_CONST_RE = re.compile(
    r'constexpr\s+(?:size_t|int|int32_t|int64_t|uint8_t)\s+(\w+)\s*=\s*'
    r'(0x[0-9A-Fa-f]+|\d+)')


def _strip_c_comments(text: str) -> str:
    text = re.sub(r'//[^\n]*', '', text)
    return re.sub(r'/\*.*?\*/', '', text, flags=re.S)


def _balanced_span(text: str, start: int, open_ch='{', close_ch='}'):
    """(inner, end_index) of the balanced open/close group whose opener
    is at/after ``start``; (None, start) when there is none."""
    i = text.find(open_ch, start)
    if i < 0:
        return None, start
    depth = 0
    for j in range(i, len(text)):
        if text[j] == open_ch:
            depth += 1
        elif text[j] == close_ch:
            depth -= 1
            if depth == 0:
                return text[i + 1:j], j
    return None, start


def _proto_fn_bodies(text: str):
    """``{name: body}`` of the inline Encode*/Decode* free functions
    (comment-stripped, brace-matched)."""
    text = _strip_c_comments(text)
    out = {}
    for m in _PROTO_FN_RE.finditer(text):
        params, end = _balanced_span(text, m.end() - 1, '(', ')')
        if params is None:
            continue
        body, _ = _balanced_span(text, end)
        if body is not None:
            out[m.group(1)] = body
    return out


def _strip_loops(body: str) -> str:
    """Remove every ``for(...)`` loop (header + body) — what remains is
    the straight-line, executed-exactly-once part of the function."""
    out = []
    i = 0
    while True:
        m = re.search(r'\bfor\s*\(', body[i:])
        if not m:
            out.append(body[i:])
            return ''.join(out)
        out.append(body[i:i + m.start()])
        _, hdr_end = _balanced_span(body, i + m.end() - 1, '(', ')')
        j = hdr_end + 1
        while j < len(body) and body[j] in ' \t\n':
            j += 1
        if j < len(body) and body[j] == '{':
            _, blk_end = _balanced_span(body, j)
            i = blk_end + 1
        else:
            k = body.find(';', j)
            i = (k + 1) if k >= 0 else len(body)


def _call_tokens(body: str, call_re):
    """Ordered (kind, arg) primitive tokens of an encoder/decoder body.
    Nested ``EncodeX(w, …)`` / ``DecodeX(rd)`` becomes ``("call", "X")``;
    writes to side buffers (``EncodeX(kw, …)``) are not frame fields and
    do not appear. Args are captured with balanced parens (casts)."""
    toks = []
    for m in call_re.finditer(body):
        if m.group(1):
            arg, _ = _balanced_span(body, m.end() - 1, '(', ')')
            toks.append((m.group(1), (arg or "").strip()))
        else:
            toks.append(("call", m.group(2)))
    return toks


def _enc_tokens(body: str):
    return _call_tokens(body, _ENC_TOK_RE)


def _dec_tokens(body: str):
    """``count`` reads an i32 length field on the wire."""
    return _call_tokens(body, _DEC_TOK_RE)


def _min_encoded_sizes(bodies: dict):
    """Grammar-derived minimum encoded size per struct: the byte cost
    of the loop-stripped ``Encode<Name>`` body (variable-length fields
    contribute their length prefix; nested encodes recurse)."""
    enc = {name[len("Encode"):]: _enc_tokens(_strip_loops(body))
           for name, body in bodies.items() if name.startswith("Encode")}
    mins = {}

    def size_of(name, stack=()):
        if name in mins:
            return mins[name]
        if name not in enc or name in stack:
            return None
        total = 0
        for kind, arg in enc[name]:
            if kind == "call":
                sub = size_of(arg, stack + (name,))
                if sub is None:
                    return None
                total += sub
            else:
                total += _WIRE_TOK_BYTES[kind]
        mins[name] = total
        return total

    for name in enc:
        size_of(name)
    return mins


def _eval_const_expr(expr: str, consts: dict):
    """Integer value of a count() bound: a literal, a constexpr name,
    or a ``name + literal`` sum. None when it cannot be resolved."""
    total = 0
    for term in expr.split('+'):
        term = term.strip()
        if not term:
            return None
        if re.fullmatch(r'\d+', term):
            total += int(term)
        elif re.fullmatch(r'0x[0-9A-Fa-f]+', term):
            total += int(term, 16)
        elif term in consts:
            total += consts[term]
        else:
            return None
    return total


def _loop_elem_bytes(body: str, after: int, var: str, containers: set,
                     mins: dict):
    """Minimum encoded bytes of one element of the loop that consumes
    ``var`` (or iterates a container sized by it): the token cost of
    the first matching ``for`` body after position ``after``. None when
    no such loop exists or a nested decode is unknown."""
    for m in re.finditer(r'\bfor\s*\(', body[after:]):
        start = after + m.start()
        hdr, hdr_end = _balanced_span(body, after + m.end() - 1, '(', ')')
        if hdr is None:
            return None
        names = set(re.findall(r'[A-Za-z_][A-Za-z0-9_.]*', hdr))
        if var not in names and not (containers & names):
            continue
        j = hdr_end + 1
        while j < len(body) and body[j] in ' \t\n':
            j += 1
        if j < len(body) and body[j] == '{':
            loop_body, _ = _balanced_span(body, j)
        else:
            loop_body = body[j:body.find(';', j) + 1]
        total = 0
        for kind, arg in _dec_tokens(loop_body or ""):
            if kind == "call":
                if mins.get(arg) is None:
                    return None
                total += mins[arg]
            elif kind == "count":
                total += 4
            else:
                total += _WIRE_TOK_BYTES[kind]
        return total if total > 0 else None
    return None


def check_proto(root: Path):
    vios = []
    wire = _read(root, WIRE_H, vios, "proto")
    if wire is None:
        return vios
    bodies = _proto_fn_bodies(wire)
    consts = {n: int(v, 0)
              for n, v in _PROTO_CONST_RE.findall(_strip_c_comments(wire))}
    mins = _min_encoded_sizes(bodies)

    # rule 1: encoder↔decoder field symmetry. A leading flag-registry
    # u8 the decoder never reads is the dispatch byte (the engine
    # consumes it to pick the decoder — DecodeAggregateFrame's
    # contract) and is allowed.
    for name, body in sorted(bodies.items()):
        if not name.startswith("Encode"):
            continue
        struct = name[len("Encode"):]
        dec_body = bodies.get("Decode" + struct)
        if dec_body is None:
            continue
        enc = [("i32" if k == "count" else k, a)
               for k, a in _enc_tokens(body)]
        dec = [("i32" if k == "count" else k, a)
               for k, a in _dec_tokens(dec_body)]
        enc_kinds = [k for k, _ in enc]
        dec_kinds = [k for k, _ in dec]
        if enc_kinds != dec_kinds:
            if (enc and enc[0][0] == "u8"
                    and re.match(r'k\w*Flag', enc[0][1] or "")
                    and enc_kinds[1:] == dec_kinds):
                continue
            vios.append(
                f"proto: {WIRE_H}: Encode{struct} writes "
                f"[{', '.join(enc_kinds)}] but Decode{struct} reads "
                f"[{', '.join(dec_kinds)}] — encoder/decoder field "
                f"symmetry broken (a peer running this build would "
                f"mis-frame the stream)")

    # rule 2: every decoder-side list allocation is sized through
    # Reader::count, and each count() bound equals the grammar-derived
    # minimum encoded size of one element of the loop it feeds.
    for name, body in sorted(bodies.items()):
        if not name.startswith("Decode"):
            continue
        counts = list(_COUNT_ASSIGN_RE.finditer(body))
        safe = {m.group(1) for m in counts}
        sized = {}  # count var -> containers it sizes
        for m in _RESIZE_RE.finditer(body):
            expr = m.group(1).strip()
            if expr not in safe:
                vios.append(
                    f"proto: {WIRE_H}: {name} resizes from '{expr}', "
                    f"which is not routed through Reader::count — a "
                    f"corrupt length would size an allocation before "
                    f"any bounds check")
        # container name left of `.resize(var)` — range-for loops over
        # it consume the counted elements
        for m in re.finditer(r'([\w.]+)\.resize\(\s*(\w+)\s*\)', body):
            sized.setdefault(m.group(2), set()).add(
                m.group(1).split('.')[-1])
        for m in _VEC_ALLOC_RE.finditer(body):
            if m.group(2) not in safe:
                vios.append(
                    f"proto: {WIRE_H}: {name} constructs "
                    f"'{m.group(1)}' sized by '{m.group(2)}', which is "
                    f"not routed through Reader::count — a corrupt "
                    f"length would size an allocation before any "
                    f"bounds check")
            else:
                sized.setdefault(m.group(2), set()).add(m.group(1))
        for m in counts:
            var, bound_expr = m.group(1), m.group(2).strip()
            declared = _eval_const_expr(bound_expr, consts)
            if declared is None:
                vios.append(
                    f"proto: {WIRE_H}: {name} uses rd.count"
                    f"({bound_expr}) — bound not resolvable to an "
                    f"integer (use a literal or a wire.h constexpr)")
                continue
            derived = _loop_elem_bytes(body, m.end(), var,
                                       sized.get(var, set()), mins)
            if derived is not None and derived != declared:
                vios.append(
                    f"proto: {WIRE_H}: {name} bounds rd.count"
                    f"({bound_expr}) = {declared}, but the element "
                    f"grammar it decodes occupies at least {derived} "
                    f"bytes — update the bound (a too-small bound "
                    f"over-allows attacker-sized allocations; too "
                    f"large rejects valid frames)")

    # rule 3: the Reader containment boundary. wire.h may memcpy /
    # reinterpret_cast only inside its Writer/Reader class bodies; no
    # other csrc file may define a Reader/Writer (the transport.h
    # Reader2 fork) or read frames with cursor-style memcpy.
    wire_nc = _strip_c_comments(wire)
    spans = []
    for m in re.finditer(r'\bclass\s+(?:Reader|Writer)\b', wire_nc):
        body, end = _balanced_span(wire_nc, m.end())
        if body is not None:
            spans.append((m.start(), end))
    outside = list(wire_nc)
    for a, b in spans:
        outside[a:b + 1] = ' ' * (b + 1 - a)
    outside = ''.join(outside)
    for pat, what in ((r'\bmemcpy\s*\(', "memcpy"),
                      (r'\breinterpret_cast\s*<', "reinterpret_cast")):
        if re.search(pat, outside):
            vios.append(
                f"proto: {WIRE_H}: {what} outside the Writer/Reader "
                f"class bodies — all frame-buffer byte access must go "
                f"through the bounds-checked Reader")
    csrc = root / CSRC_DIR
    if csrc.is_dir():
        for p in sorted(csrc.iterdir()):
            if p.suffix not in (".h", ".cc") or p.name == "wire.h":
                continue
            text = _strip_c_comments(p.read_text())
            for m in _READER_FORK_RE.finditer(text):
                vios.append(
                    f"proto: {CSRC_DIR}/{p.name}: defines "
                    f"'{m.group(1)}' — frame readers/writers live in "
                    f"wire.h ONLY (a fork re-opens the unbounded-read "
                    f"class Reader::count closed)")
            if p.name == "transport.h" and re.search(r'memcpy\s*\(\s*&',
                                                     text):
                vios.append(
                    f"proto: {CSRC_DIR}/{p.name}: cursor-style "
                    f"memcpy(&…) frame read — session frames must be "
                    f"parsed with the wire.h Reader")

    # rule 4: flag bytes are tested against registry names, never
    # numeric literals (a literal can silently collide with a
    # registry bit — including the abort bit).
    for rel in (WIRE_H, TRANSPORT_H, ENGINE_CC, ENGINE_H):
        p = root / rel
        if not p.is_file():
            continue
        for m in _FLAG_LITERAL_RE.finditer(_strip_c_comments(
                p.read_text())):
            vios.append(
                f"proto: {rel}: flag byte tested against literal "
                f"{m.group(1)} — use the wire.h registry constant "
                f"(kCtrlFlag*/kRespFlag*/kAbortFrameFlag)")

    # rule 5: Python-side decoders match their documented framing.
    state_p = root / STATE_PY
    if state_p.is_file():
        state = state_p.read_text()
        decode = re.search(r'\ndef decode_shard\b.*?(?=\ndef |\Z)',
                           state, re.S)
        if "_SHARD_HEADER" not in state or decode is None:
            vios.append(
                f"proto: {STATE_PY}: shard framing must be the single "
                f"_SHARD_HEADER Struct shared by encode_shard and "
                f"decode_shard")
        else:
            for needle, why in (
                    ("_SHARD_HEADER", "parse the shared header Struct"),
                    ("_SHARD_MAGIC", "check the magic"),
                    ("crc32", "verify the payload CRC"),
                    ("ShardCorruptError", "raise the typed rejection")):
                if needle not in decode.group(0):
                    vios.append(
                        f"proto: {STATE_PY}: decode_shard does not "
                        f"{why} ({needle}) — the shard frame would "
                        f"decode without its documented validation")
    telem_p, http_p = root / TELEMETRY_PY, root / HTTP_SERVER_PY
    if telem_p.is_file() and http_p.is_file():
        telem, http = telem_p.read_text(), http_p.read_text()
        for key in ("scope", "key", "value_b64"):
            missing = [rel for rel, text in ((TELEMETRY_PY, telem),
                                             (HTTP_SERVER_PY, http))
                       if f'"{key}"' not in text]
            for rel in missing:
                vios.append(
                    f"proto: {rel}: kvbulk envelope key \"{key}\" "
                    f"missing — producer (telemetry) and consumer "
                    f"(http_server) must agree on the envelope "
                    f"framing")
    return vios


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

PASSES = {
    "capi": check_capi,
    "slots": check_slots,
    "events": check_events,
    "env": check_env,
    "codecs": check_codecs,
    "proto": check_proto,
}


def run(root: Path, passes=None) -> list:
    """All violations from the selected passes (default: all)."""
    out = []
    for name in (passes or PASSES):
        out.extend(PASSES[name](root))
    return out


def main(argv=None) -> int:
    default_root = Path(__file__).resolve().parents[2]
    ap = argparse.ArgumentParser(
        prog="hvt_lint",
        description="cross-language contract linter (C API / stats-slot "
                    "ABI / event kinds / frame flags / env docs)")
    ap.add_argument("passes", nargs="*", choices=[[], *PASSES],
                    help=f"subset of passes ({', '.join(PASSES)}); "
                         f"default all")
    ap.add_argument("--root", type=Path, default=default_root,
                    help="repo root to lint (default: this checkout)")
    ap.add_argument("--emit-symbols", action="store_true",
                    help="print the canonical extern-C symbol list "
                         "(one per line) and exit — consumed by ci.sh's "
                         "nm -D export check")
    args = ap.parse_args(argv)
    if args.emit_symbols:
        try:
            print("\n".join(c_api_symbols(args.root)))
        except OSError as e:
            print(f"hvt-lint: cannot read {C_API_CC}: {e}",
                  file=sys.stderr)
            return 2
        return 0
    vios = run(args.root, args.passes or None)
    for v in vios:
        print(f"hvt-lint: {v}")
    names = ", ".join(args.passes or PASSES)
    if vios:
        print(f"hvt-lint: FAILED — {len(vios)} violation(s) "
              f"[{names}]")
        return 1
    print(f"hvt-lint: OK [{names}]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
