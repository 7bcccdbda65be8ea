"""Unit tests for the cross-language contract linter
(horovod_tpu/tools/hvt_lint.py).

Strategy: build a MINIMAL but fully consistent fixture tree (tiny
c_api.cc / stats manifest / native.py / basics.py / events.h /
timeline.py / wire.h / docs), assert the lint passes it clean, then
seed one violation per test and assert the lint fails with a pointed
message. A final test asserts the REAL tree passes every pass — that
is the tier-1 contract gate itself.
"""

import os
import textwrap
from pathlib import Path

from horovod_tpu.tools import hvt_lint

REPO_ROOT = Path(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _write(root: Path, rel: str, text: str):
    p = root / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(text))


def make_clean_tree(root: Path):
    """A consistent mini-repo: 2 C symbols, a 13-slot stats ABI
    (2 scalars, 1 op, 1+2-slot histograms, 1 abort cause), 2 event
    kinds, 2 frame flags, 1 documented env knob."""
    _write(root, hvt_lint.C_API_CC, """\
        #include "stats_slots.h"
        constexpr int kStatsScalars = 2;
        static_assert(13 == HVT_STATS_SLOT_COUNT, "slots are append-only");
        extern "C" {
        int hvt_init(int rank) { return rank; }
        int hvt_poll(int h) { return h; }
        }
        """)
    _write(root, hvt_lint.ENGINE_H, """\
        constexpr int kStatsOps = 1;
        constexpr int kLatBuckets = 0;
        constexpr int kAbortCauses = 1;
        """)
    _write(root, hvt_lint.STATS_SLOTS_H, """\
        #define HVT_STATS_SLOT_COUNT 13
        #define HVT_STATS_SLOTS(X) \\
          X(0, "a") \\
          X(1, "b") \\
          X(2, "exec_ns[allreduce]") \\
          X(3, "exec_count[allreduce]") \\
          X(4, "wire_tx_bytes[allreduce]") \\
          X(5, "wire_tx_comp_bytes[allreduce]") \\
          X(6, "cycle_hist.bucket[0]") \\
          X(7, "cycle_hist.sum_ns") \\
          X(8, "cycle_hist.count") \\
          X(9, "wakeup_hist.bucket[0]") \\
          X(10, "wakeup_hist.sum_ns") \\
          X(11, "wakeup_hist.count") \\
          X(12, "aborts[internal]")
        """)
    _write(root, hvt_lint.NATIVE_PY, """\
        STATS_SCALARS = ("a", "b")
        STATS_OPS = ("allreduce",)
        STATS_LAT_BUCKETS = 0
        ABORT_CAUSES = ("internal",)
        EVENT_KINDS = ("ENQUEUED", "DONE")


        def bind(lib):
            lib.hvt_init(0)
            return lib.hvt_poll(0)
        """)
    _write(root, hvt_lint.BASICS_PY, """\
        import os

        _KNOB = os.environ.get("HVT_FOO")


        def poll_engine_stats(stats):
            return [stats.get(k) for k in (
                "a", "b", "exec_ns", "exec_count", "wire_tx_bytes",
                "wire_tx_comp_bytes", "cycle_hist", "wakeup_hist",
                "aborts")]
        """)
    _write(root, hvt_lint.EVENTS_H, """\
        enum class EventKind : int32_t {
          ENQUEUED = 0,
          DONE = 1,
        };
        """)
    _write(root, hvt_lint.TIMELINE_PY, """\
        _ENQUEUED, _DONE = range(2)


        def drain(kind):
            if kind == _ENQUEUED:
                return "enqueued"
            if kind == _DONE:
                return "done"
            return None
        """)
    _write(root, hvt_lint.WIRE_H, """\
        constexpr uint8_t kCtrlFlagShutdown = 0x01;
        constexpr uint8_t kAbortFrameFlag = 0x80;
        """)
    _write(root, hvt_lint.ENGINE_CC, """\
        #include "wire.h"
        int use_flags() { return kCtrlFlagShutdown | kAbortFrameFlag; }
        """)
    _write(root, "docs/index.md", """\
        # Mini docs

        - `HVT_FOO`: the one knob of the fixture tree.
        """)


def test_fixture_tree_is_clean(tmp_path):
    make_clean_tree(tmp_path)
    assert hvt_lint.run(tmp_path) == []


# ---------------------------------------------------------------- capi

def test_unbound_c_symbol_fails(tmp_path):
    make_clean_tree(tmp_path)
    p = tmp_path / hvt_lint.C_API_CC
    p.write_text(p.read_text().replace(
        "int hvt_poll",
        "int hvt_orphan(int x) { return x; }\nint hvt_poll"))
    vios = hvt_lint.check_capi(tmp_path)
    assert any("hvt_orphan" in v and "bound nowhere" in v for v in vios), vios


def test_binding_unknown_symbol_fails(tmp_path):
    make_clean_tree(tmp_path)
    p = tmp_path / hvt_lint.NATIVE_PY
    p.write_text(p.read_text() + "\n\ndef bad(lib):\n"
                                 "    return lib.hvt_ghost()\n")
    vios = hvt_lint.check_capi(tmp_path)
    assert any("hvt_ghost" in v and "does not define" in v
               for v in vios), vios


def test_emit_symbols_lists_the_extern_c_surface(tmp_path):
    make_clean_tree(tmp_path)
    assert hvt_lint.c_api_symbols(tmp_path) == ["hvt_init", "hvt_poll"]


# --------------------------------------------------------------- slots

def test_reused_slot_index_fails(tmp_path):
    make_clean_tree(tmp_path)
    p = tmp_path / hvt_lint.STATS_SLOTS_H
    p.write_text(p.read_text().replace('X(1, "b")', 'X(0, "b")'))
    vios = hvt_lint.check_slots(tmp_path)
    assert any("never be reused" in v for v in vios), vios


def test_slot_count_drift_fails(tmp_path):
    make_clean_tree(tmp_path)
    p = tmp_path / hvt_lint.STATS_SLOTS_H
    p.write_text(p.read_text().replace(
        "#define HVT_STATS_SLOT_COUNT 13",
        "#define HVT_STATS_SLOT_COUNT 14"))
    vios = hvt_lint.check_slots(tmp_path)
    assert any("HVT_STATS_SLOT_COUNT" in v for v in vios), vios


def test_manifest_python_layout_mismatch_fails(tmp_path):
    make_clean_tree(tmp_path)
    p = tmp_path / hvt_lint.STATS_SLOTS_H
    p.write_text(p.read_text().replace('X(1, "b")', 'X(1, "renamed")'))
    vios = hvt_lint.check_slots(tmp_path)
    assert any("does not match" in v and "layout" in v for v in vios), vios


def _add_lane_slots(root: Path, n=2):
    """Extend the clean fixture with an n-bucket lane block (the PR-6
    per-set lane telemetry appendix): engine.h kLaneSlots, native.py
    STATS_LANE_SLOTS, manifest rows, and the bridge reads."""
    eh = root / hvt_lint.ENGINE_H
    eh.write_text(eh.read_text() + f"constexpr int kLaneSlots = {n};\n")
    np_ = root / hvt_lint.NATIVE_PY
    np_.write_text(f"STATS_LANE_SLOTS = {n}\n" + np_.read_text())
    rows = ['  X(13, "lanes_active")']
    idx = 14
    for grp in hvt_lint.SLOT_LANE_GROUPS:
        for i in range(n):
            rows.append(f'  X({idx}, "{grp}[{i}]")')
            idx += 1
    sl = root / hvt_lint.STATS_SLOTS_H
    sl.write_text(sl.read_text()
                  .replace("#define HVT_STATS_SLOT_COUNT 13",
                           f"#define HVT_STATS_SLOT_COUNT {idx}")
                  .rstrip("\n") + " \\\n" + " \\\n".join(rows) + "\n")
    ca = root / hvt_lint.C_API_CC
    ca.write_text(ca.read_text().replace(
        "static_assert(13 ==", f"static_assert({idx} =="))
    bp = root / hvt_lint.BASICS_PY
    bp.write_text(bp.read_text().replace(
        '"aborts")', '"aborts", "lanes_active", "lane_depth", '
                     '"lane_exec_ns", "lane_exec_count")'))


def test_lane_slot_fixture_is_clean(tmp_path):
    make_clean_tree(tmp_path)
    _add_lane_slots(tmp_path)
    assert hvt_lint.check_slots(tmp_path) == []


def test_lane_slot_count_mismatch_fails(tmp_path):
    """engine.h kLaneSlots drifting from native.py STATS_LANE_SLOTS
    would decode the lane blocks shifted — the lint must catch it."""
    make_clean_tree(tmp_path)
    _add_lane_slots(tmp_path)
    p = tmp_path / hvt_lint.NATIVE_PY
    p.write_text(p.read_text().replace("STATS_LANE_SLOTS = 2",
                                       "STATS_LANE_SLOTS = 3"))
    vios = hvt_lint.check_slots(tmp_path)
    assert any("kLaneSlots" in v for v in vios), vios


def _add_tail_scalars(root: Path):
    """Extend the clean fixture with a 1-slot trailing scalar block (the
    PR-7 ctrl-bytes appendix shape): c_api.cc kStatsTailScalars,
    native.py STATS_TAIL_SCALARS, a manifest row, and the bridge read."""
    ca = root / hvt_lint.C_API_CC
    ca.write_text(ca.read_text()
                  .replace("constexpr int kStatsScalars = 2;",
                           "constexpr int kStatsScalars = 2;\n"
                           "constexpr int kStatsTailScalars = 1;")
                  .replace("static_assert(13 ==", "static_assert(14 =="))
    np_ = root / hvt_lint.NATIVE_PY
    np_.write_text('STATS_TAIL_SCALARS = ("tail_z",)\n' + np_.read_text())
    sl = root / hvt_lint.STATS_SLOTS_H
    sl.write_text(sl.read_text()
                  .replace("#define HVT_STATS_SLOT_COUNT 13",
                           "#define HVT_STATS_SLOT_COUNT 14")
                  .rstrip("\n") + ' \\\n  X(13, "tail_z")\n')
    bp = root / hvt_lint.BASICS_PY
    bp.write_text(bp.read_text().replace('"aborts")', '"aborts", "tail_z")'))


def test_tail_scalar_fixture_is_clean(tmp_path):
    make_clean_tree(tmp_path)
    _add_tail_scalars(tmp_path)
    assert hvt_lint.check_slots(tmp_path) == []


def test_tail_scalar_count_mismatch_fails(tmp_path):
    """c_api.cc kStatsTailScalars drifting from native.py
    STATS_TAIL_SCALARS would decode the trailing block shifted."""
    make_clean_tree(tmp_path)
    _add_tail_scalars(tmp_path)
    p = tmp_path / hvt_lint.C_API_CC
    p.write_text(p.read_text().replace("kStatsTailScalars = 1",
                                       "kStatsTailScalars = 2"))
    vios = hvt_lint.check_slots(tmp_path)
    assert any("kStatsTailScalars" in v for v in vios), vios


def _add_link_slots(root: Path):
    """Extend the clean fixture with the self-healing link appendix
    (PR-10 shape): c_api.cc kStatsLinkPlanes/kStatsRecoveryScalars,
    native.py STATS_LINK_PLANES/STATS_RECOVERY_SCALARS, manifest rows,
    and the bridge reads."""
    ca = root / hvt_lint.C_API_CC
    ca.write_text(ca.read_text()
                  .replace("constexpr int kStatsScalars = 2;",
                           "constexpr int kStatsScalars = 2;\n"
                           "constexpr int kStatsLinkPlanes = 2;\n"
                           "constexpr int kStatsRecoveryScalars = 1;")
                  .replace("static_assert(13 ==", "static_assert(16 =="))
    np_ = root / hvt_lint.NATIVE_PY
    np_.write_text('STATS_LINK_PLANES = ("ctrl", "data")\n'
                   'STATS_RECOVERY_SCALARS = ("replay_z",)\n'
                   + np_.read_text())
    sl = root / hvt_lint.STATS_SLOTS_H
    sl.write_text(sl.read_text()
                  .replace("#define HVT_STATS_SLOT_COUNT 13",
                           "#define HVT_STATS_SLOT_COUNT 16")
                  .rstrip("\n") + ' \\\n  X(13, "link_reconnects[ctrl]")'
                  ' \\\n  X(14, "link_reconnects[data]")'
                  ' \\\n  X(15, "replay_z")\n')
    bp = root / hvt_lint.BASICS_PY
    bp.write_text(bp.read_text().replace(
        '"aborts")', '"aborts", "link_reconnects", "replay_z")'))


def test_link_slot_fixture_is_clean(tmp_path):
    make_clean_tree(tmp_path)
    _add_link_slots(tmp_path)
    assert hvt_lint.check_slots(tmp_path) == []


def test_link_plane_count_mismatch_fails(tmp_path):
    """c_api.cc kStatsLinkPlanes drifting from native.py
    STATS_LINK_PLANES would decode the reconnect block shifted."""
    make_clean_tree(tmp_path)
    _add_link_slots(tmp_path)
    p = tmp_path / hvt_lint.C_API_CC
    p.write_text(p.read_text().replace("kStatsLinkPlanes = 2",
                                       "kStatsLinkPlanes = 3"))
    vios = hvt_lint.check_slots(tmp_path)
    assert any("kStatsLinkPlanes" in v for v in vios), vios


def test_recovery_scalar_count_mismatch_fails(tmp_path):
    """c_api.cc kStatsRecoveryScalars drifting from native.py
    STATS_RECOVERY_SCALARS would decode the replay block shifted."""
    make_clean_tree(tmp_path)
    _add_link_slots(tmp_path)
    p = tmp_path / hvt_lint.C_API_CC
    p.write_text(p.read_text().replace("kStatsRecoveryScalars = 1",
                                       "kStatsRecoveryScalars = 2"))
    vios = hvt_lint.check_slots(tmp_path)
    assert any("kStatsRecoveryScalars" in v for v in vios), vios


def test_unread_link_slot_group_fails(tmp_path):
    """A manifest slot group (link_reconnects) nobody reads in
    poll_engine_stats is telemetry silently thrown away."""
    make_clean_tree(tmp_path)
    _add_link_slots(tmp_path)
    bp = tmp_path / hvt_lint.BASICS_PY
    bp.write_text(bp.read_text().replace('"link_reconnects"',
                                         '"link_ignored"'))
    vios = hvt_lint.check_slots(tmp_path)
    assert any('never reads "link_reconnects"' in v for v in vios), vios


def test_unread_slot_group_fails(tmp_path):
    make_clean_tree(tmp_path)
    p = tmp_path / hvt_lint.BASICS_PY
    p.write_text(p.read_text().replace('"aborts"', '"ignored"'))
    vios = hvt_lint.check_slots(tmp_path)
    assert any('never reads "aborts"' in v for v in vios), vios


# -------------------------------------------------------------- events

def test_undrained_event_kind_fails(tmp_path):
    make_clean_tree(tmp_path)
    p = tmp_path / hvt_lint.TIMELINE_PY
    body = p.read_text().replace(
        '    if kind == _DONE:\n        return "done"\n', "")
    p.write_text(body)
    vios = hvt_lint.check_events(tmp_path)
    assert any("DONE" in v and "never referenced by the drainer" in v
               for v in vios), vios


def test_event_kind_tuple_drift_fails(tmp_path):
    make_clean_tree(tmp_path)
    p = tmp_path / hvt_lint.NATIVE_PY
    p.write_text(p.read_text().replace(
        'EVENT_KINDS = ("ENQUEUED", "DONE")',
        'EVENT_KINDS = ("ENQUEUED",)'))
    vios = hvt_lint.check_events(tmp_path)
    assert any("EVENT_KINDS" in v for v in vios), vios


def test_frame_flag_bit_collision_fails(tmp_path):
    make_clean_tree(tmp_path)
    p = tmp_path / hvt_lint.WIRE_H
    p.write_text(p.read_text()
                 + "constexpr uint8_t kCtrlFlagJoin = 0x80;\n")
    cc = tmp_path / hvt_lint.ENGINE_CC
    cc.write_text(cc.read_text().replace(
        "kCtrlFlagShutdown |", "kCtrlFlagShutdown | kCtrlFlagJoin |"))
    vios = hvt_lint.check_events(tmp_path)
    assert any("both claim bit 0x80" in v for v in vios), vios


def test_flag_defined_outside_registry_fails(tmp_path):
    make_clean_tree(tmp_path)
    cc = tmp_path / hvt_lint.ENGINE_CC
    cc.write_text("constexpr uint8_t kAbortFrameFlag = 0x80;\n"
                  + cc.read_text())
    vios = hvt_lint.check_events(tmp_path)
    assert any("re-defines kAbortFrameFlag" in v for v in vios), vios


def _add_ctrl_roles(root: Path):
    """Extend the clean fixture with the control-plane role registry
    (PR-8 hierarchical negotiation): engine.h CtrlRole ↔ timeline.py
    CTRL_ROLES."""
    eh = root / hvt_lint.ENGINE_H
    eh.write_text(eh.read_text() + """\
enum class CtrlRole : int32_t {
  ROOT = 0,
  LEADER = 1,
  MEMBER = 2,
};
""")
    tl = root / hvt_lint.TIMELINE_PY
    tl.write_text('CTRL_ROLES = ("root", "leader", "member")\n'
                  + tl.read_text())


def test_ctrl_role_fixture_is_clean(tmp_path):
    make_clean_tree(tmp_path)
    _add_ctrl_roles(tmp_path)
    assert hvt_lint.check_events(tmp_path) == []


def test_ctrl_role_registry_drift_fails(tmp_path):
    """timeline.py CTRL_ROLES drifting from engine.h CtrlRole (here a
    reordered pair) must fail — CTRL instants would attribute control
    bytes to the wrong role."""
    make_clean_tree(tmp_path)
    _add_ctrl_roles(tmp_path)
    tl = tmp_path / hvt_lint.TIMELINE_PY
    tl.write_text(tl.read_text().replace(
        '("root", "leader", "member")', '("root", "member", "leader")'))
    vios = hvt_lint.check_events(tmp_path)
    assert any("CTRL_ROLES" in v and "wrong role" in v
               for v in vios), vios


def test_ctrl_role_one_sided_registry_fails(tmp_path):
    """CTRL_ROLES without the C++ enum (or vice versa) is a violation:
    the registry is a cross-language contract, not a constant."""
    make_clean_tree(tmp_path)
    tl = tmp_path / hvt_lint.TIMELINE_PY
    tl.write_text('CTRL_ROLES = ("root", "leader", "member")\n'
                  + tl.read_text())
    vios = hvt_lint.check_events(tmp_path)
    assert any("no enum class CtrlRole" in v for v in vios), vios


# ----------------------------------------------------------------- env

def test_undocumented_env_read_fails(tmp_path):
    make_clean_tree(tmp_path)
    _write(tmp_path, "horovod_tpu/runner/launch.py", """\
        import os

        SECRET = os.environ.get("HVT_SECRET")
        """)
    vios = hvt_lint.check_env(tmp_path)
    assert any("HVT_SECRET" in v and "documented nowhere" in v
               for v in vios), vios


def test_stale_env_doc_row_fails(tmp_path):
    make_clean_tree(tmp_path)
    _write(tmp_path, "docs/ghost.md", "`HVT_GHOST` does nothing now.\n")
    vios = hvt_lint.check_env(tmp_path)
    assert any("HVT_GHOST" in v and "no code reads it" in v
               for v in vios), vios


# ------------------------------------------------- codec registry pass

def make_codec_tree(root: Path):
    """Minimal consistent codec registry: 2 codecs across codecs.h, the
    compression name table, native.py WIRE_CODECS, and the docs codec
    table."""
    make_clean_tree(root)
    _write(root, hvt_lint.CODECS_H, """\
        #define HVT_WIRE_CODECS(X) \\
          X(0, "none")             \\
          X(1, "bf16")
        enum class WireCodec : uint8_t {
          RAW = 0,
          BF16 = 1,
        };
        constexpr int kWireCodecCount = 2;
        """)
    _write(root, hvt_lint.COMPRESSION_PY, """\
        CODEC_IDS = {"none": 0, "bf16": 1}
        """)
    _write(root, hvt_lint.NATIVE_PY, """\
        STATS_SCALARS = ("a", "b")
        STATS_OPS = ("allreduce",)
        STATS_LAT_BUCKETS = 0
        ABORT_CAUSES = ("internal",)
        EVENT_KINDS = ("ENQUEUED", "DONE")
        WIRE_CODECS = ("none", "bf16")


        def bind(lib):
            lib.hvt_init(0)
            return lib.hvt_poll(0)
        """)
    _write(root, hvt_lint.PERFORMANCE_MD, """\
        # Perf

        #### Codec table

        | codec | ratio |
        |---|---|
        | `none` | 1x |
        | `bf16` | 2x |
        """)


def test_codec_fixture_is_clean(tmp_path):
    make_codec_tree(tmp_path)
    # codec rows are absent from the fixture stats manifest, so run the
    # codecs pass alone (slots stays covered by its own fixtures)
    assert hvt_lint.check_codecs(tmp_path) == []


def test_codec_registry_absent_is_fine(tmp_path):
    make_clean_tree(tmp_path)
    assert hvt_lint.check_codecs(tmp_path) == []


def test_codec_python_table_drift_fails(tmp_path):
    make_codec_tree(tmp_path)
    _write(tmp_path, hvt_lint.COMPRESSION_PY, """\
        CODEC_IDS = {"none": 0, "bf16": 2}
        """)
    vios = hvt_lint.check_codecs(tmp_path)
    assert any("CODEC_IDS" in v and "does not match" in v
               for v in vios), vios


def test_codec_native_tuple_drift_fails(tmp_path):
    make_codec_tree(tmp_path)
    text = (tmp_path / hvt_lint.NATIVE_PY).read_text()
    _write(tmp_path, hvt_lint.NATIVE_PY,
           text.replace('WIRE_CODECS = ("none", "bf16")',
                        'WIRE_CODECS = ("none",)'))
    vios = hvt_lint.check_codecs(tmp_path)
    assert any("WIRE_CODECS" in v for v in vios), vios


def test_codec_id_renumber_fails(tmp_path):
    make_codec_tree(tmp_path)
    text = (tmp_path / hvt_lint.CODECS_H).read_text()
    _write(tmp_path, hvt_lint.CODECS_H,
           text.replace('X(1, "bf16")', 'X(2, "bf16")'))
    vios = hvt_lint.check_codecs(tmp_path)
    assert any("contiguous" in v for v in vios), vios


def test_codec_docs_table_drift_fails(tmp_path):
    make_codec_tree(tmp_path)
    text = (tmp_path / hvt_lint.PERFORMANCE_MD).read_text()
    # stale doc row for a codec the registry no longer lists
    _write(tmp_path, hvt_lint.PERFORMANCE_MD,
           text + "| `zstd` | 9x |\n")
    vios = hvt_lint.check_codecs(tmp_path)
    assert any("codec table rows" in v for v in vios), vios


def test_codec_enum_registry_mismatch_fails(tmp_path):
    make_codec_tree(tmp_path)
    text = (tmp_path / hvt_lint.CODECS_H).read_text()
    _write(tmp_path, hvt_lint.CODECS_H,
           text.replace("BF16 = 1,", "BF16 = 3,"))
    vios = hvt_lint.check_codecs(tmp_path)
    assert any("enum" in v and "registry" in v for v in vios), vios


# ---------------------------------------------------- proto pass


def make_proto_tree(root: Path):
    """Mini tree for the wire-grammar pass: one symmetric Encode/Decode
    pair (2 fixed fields + a counted str list, min element 4 bytes), a
    count()-routed list decoder, a clean transport.h, and the Python
    framing files."""
    make_clean_tree(root)
    _write(root, hvt_lint.WIRE_H, """\
        constexpr uint8_t kCtrlFlagShutdown = 0x01;
        constexpr uint8_t kAbortFrameFlag = 0x80;
        constexpr size_t kMinEncodedPingBytes = 16;

        class Writer {
         public:
          void append(const void* p, size_t n) { memcpy(0, p, n); }
        };
        class Reader {
         public:
          int32_t i32() { int32_t v; memcpy(&v, 0, 4); return v; }
        };

        inline void EncodePing(Writer& w, const Ping& p) {
          w.i32(p.rank);
          w.i64(p.epoch);
          w.i32(static_cast<int32_t>(p.tags.size()));
          for (auto& t : p.tags) w.str(t);
        }

        inline Ping DecodePing(Reader& rd) {
          Ping p;
          p.rank = rd.i32();
          p.epoch = rd.i64();
          size_t n = rd.count(4);
          p.tags.resize(n);
          for (auto& t : p.tags) t = rd.str();
          return p;
        }

        inline void EncodePingList(Writer& w, const std::vector<Ping>& ps) {
          w.i32(static_cast<int32_t>(ps.size()));
          for (auto& p : ps) EncodePing(w, p);
        }

        inline std::vector<Ping> DecodePingList(Reader& rd) {
          size_t n = rd.count(kMinEncodedPingBytes);
          std::vector<Ping> ps(n);
          for (auto& p : ps) p = DecodePing(rd);
          return ps;
        }
        """)
    _write(root, hvt_lint.TRANSPORT_H, """\
        #include "wire.h"
        inline bool ReadHello(Reader& rd) { return rd.i32() == 7; }
        """)
    _write(root, hvt_lint.STATE_PY, """\
        import struct
        from zlib import crc32

        _SHARD_MAGIC = b"HVTS"
        _SHARD_HEADER = struct.Struct("<4sqiIq")


        class ShardCorruptError(RuntimeError):
            pass


        def encode_shard(payload):
            return _SHARD_HEADER.pack(_SHARD_MAGIC, 1, 0,
                                      crc32(payload), len(payload)) + payload


        def decode_shard(blob):
            magic, _v, _o, crc, n = _SHARD_HEADER.unpack_from(blob)
            if magic != _SHARD_MAGIC:
                raise ShardCorruptError("bad magic")
            payload = blob[_SHARD_HEADER.size:_SHARD_HEADER.size + n]
            if crc32(payload) != crc:
                raise ShardCorruptError("bad crc")
            return payload
        """)
    _write(root, hvt_lint.TELEMETRY_PY, """\
        def envelope(scope, key, blob):
            return {"scope": scope, "key": key, "value_b64": blob}
        """)
    _write(root, hvt_lint.HTTP_SERVER_PY, """\
        def handle_kvbulk(envs):
            return [(e["scope"], e["key"], e["value_b64"]) for e in envs]
        """)


def test_proto_fixture_tree_is_clean(tmp_path):
    make_proto_tree(tmp_path)
    assert hvt_lint.check_proto(tmp_path) == []
    assert hvt_lint.run(tmp_path) == [], hvt_lint.run(tmp_path)


def test_proto_field_symmetry_drift_fails(tmp_path):
    # encoder grows a field the decoder never reads
    make_proto_tree(tmp_path)
    text = (tmp_path / hvt_lint.WIRE_H).read_text()
    _write(tmp_path, hvt_lint.WIRE_H,
           text.replace("w.i64(p.epoch);",
                        "w.i64(p.epoch);\n  w.u8(p.plane);"))
    vios = hvt_lint.check_proto(tmp_path)
    assert any("field symmetry broken" in v and "EncodePing " in v
               for v in vios), vios


def test_proto_raw_count_resize_fails(tmp_path):
    # the DecodeResponse bug this pass was built to catch: a list
    # allocation sized straight from rd.i32()
    make_proto_tree(tmp_path)
    text = (tmp_path / hvt_lint.WIRE_H).read_text()
    _write(tmp_path, hvt_lint.WIRE_H,
           text.replace("size_t n = rd.count(4);\n  p.tags.resize(n);",
                        "int32_t n = rd.i32();\n  p.tags.resize(n);"))
    vios = hvt_lint.check_proto(tmp_path)
    assert any("not routed through Reader::count" in v
               for v in vios), vios


def test_proto_stale_count_bound_fails(tmp_path):
    # a field lands in the encoder; the paired count() bound is stale
    make_proto_tree(tmp_path)
    text = (tmp_path / hvt_lint.WIRE_H).read_text()
    _write(tmp_path, hvt_lint.WIRE_H,
           text.replace("w.i64(p.epoch);", "w.i64(p.epoch);\n  w.i64(p.t);")
               .replace("p.epoch = rd.i64();",
                        "p.epoch = rd.i64();\n  p.t = rd.i64();"))
    vios = hvt_lint.check_proto(tmp_path)
    assert any("occupies at least 24 bytes" in v for v in vios), vios


def test_proto_unresolvable_count_bound_fails(tmp_path):
    make_proto_tree(tmp_path)
    text = (tmp_path / hvt_lint.WIRE_H).read_text()
    _write(tmp_path, hvt_lint.WIRE_H,
           text.replace("rd.count(kMinEncodedPingBytes)",
                        "rd.count(sizeof(Ping))"))
    vios = hvt_lint.check_proto(tmp_path)
    assert any("not resolvable" in v for v in vios), vios


def test_proto_reader_fork_fails(tmp_path):
    # the transport.h Reader2 this PR folded away must never come back
    make_proto_tree(tmp_path)
    text = (tmp_path / hvt_lint.TRANSPORT_H).read_text()
    _write(tmp_path, hvt_lint.TRANSPORT_H, text + textwrap.dedent("""\
        struct Reader2 {
          size_t pos = 0;
          int32_t i32(const std::vector<uint8_t>& b) {
            int32_t v;
            memcpy(&v, b.data() + pos, 4);
            pos += 4;
            return v;
          }
        };
        """))
    vios = hvt_lint.check_proto(tmp_path)
    assert any("Reader2" in v and "wire.h ONLY" in v for v in vios), vios
    assert any("cursor-style" in v for v in vios), vios


def test_proto_memcpy_outside_reader_fails(tmp_path):
    make_proto_tree(tmp_path)
    text = (tmp_path / hvt_lint.WIRE_H).read_text()
    _write(tmp_path, hvt_lint.WIRE_H, text + textwrap.dedent("""\
        inline int DecodePeek(const std::vector<uint8_t>& f) {
          int32_t v;
          memcpy(&v, f.data() + 1, 4);
          return v;
        }
        """))
    vios = hvt_lint.check_proto(tmp_path)
    assert any("outside the Writer/Reader" in v for v in vios), vios


def test_proto_flag_literal_fails(tmp_path):
    make_proto_tree(tmp_path)
    text = (tmp_path / hvt_lint.ENGINE_CC).read_text()
    _write(tmp_path, hvt_lint.ENGINE_CC, text + textwrap.dedent("""\
        bool is_special(uint8_t first) { return first & 0x40; }
        """))
    vios = hvt_lint.check_proto(tmp_path)
    assert any("literal 0x40" in v and "registry" in v for v in vios), vios


def test_proto_shard_decode_validation_fails(tmp_path):
    make_proto_tree(tmp_path)
    text = (tmp_path / hvt_lint.STATE_PY).read_text()
    _write(tmp_path, hvt_lint.STATE_PY,
           text.replace("    if crc32(payload) != crc:\n"
                        "        raise ShardCorruptError(\"bad crc\")\n",
                        ""))
    vios = hvt_lint.check_proto(tmp_path)
    assert any("verify the payload CRC" in v for v in vios), vios


def test_proto_kvbulk_key_drift_fails(tmp_path):
    # producer renames an envelope key the consumer still expects
    make_proto_tree(tmp_path)
    text = (tmp_path / hvt_lint.TELEMETRY_PY).read_text()
    _write(tmp_path, hvt_lint.TELEMETRY_PY,
           text.replace('"value_b64"', '"payload_b64"'))
    vios = hvt_lint.check_proto(tmp_path)
    assert any('"value_b64"' in v and "telemetry" in v for v in vios), vios


def test_proto_real_wire_minimums_match_grammar():
    """The pinned constants in the REAL wire.h equal what the pass
    derives from the real encoder bodies — the self-checking contract
    (add a Request field → this and the proto pass both fail until
    kMinEncodedRequestBytes moves)."""
    text = (REPO_ROOT / hvt_lint.WIRE_H).read_text()
    bodies = hvt_lint._proto_fn_bodies(text)
    mins = hvt_lint._min_encoded_sizes(bodies)
    assert mins["Request"] == 51
    assert mins["Response"] == 58


# ---------------------------------------------------- the real tree

def test_real_tree_passes_every_lint_pass():
    """The tier-1 contract gate: the actual repository must be clean
    under every pass (this is what `ci.sh --lint` runs)."""
    vios = hvt_lint.run(REPO_ROOT)
    assert vios == [], "\n".join(vios)


def test_real_tree_symbol_list_covers_the_bridge():
    syms = hvt_lint.c_api_symbols(REPO_ROOT)
    # spot-check the load-bearing names ci.sh's nm gate must see
    for must in ("hvt_init", "hvt_submit", "hvt_wait", "hvt_engine_stats",
                 "hvt_events_drain", "hvt_wait_timeout",
                 "hvt_engine_broken", "hvt_wire_compression"):
        assert must in syms
    assert len(syms) >= 29


def test_stats_slot_count_matches_python_bridge():
    """The manifest's count equals what the ctypes decoder sizes its
    buffer to — the same invariant the slots pass checks by text, here
    pinned against the imported module."""
    from horovod_tpu.engine import native

    text = (REPO_ROOT / hvt_lint.STATS_SLOTS_H).read_text()
    m = hvt_lint._SLOT_COUNT_RE.search(text)
    assert m and int(m.group(1)) == native.STATS_SLOT_COUNT == 161


def test_the_compiled_step_reads_no_environment_variable():
    """Models, meshes, the optimizer wrapper, the kernels and the fused
    loss are what a training step is compiled from: what they do is
    decided by their arguments and the shapes, never by the process's
    environment (a variable read under a cached trace would be stale)."""
    sources = [p for d in ("models", "parallel", "jax")
               for p in sorted((REPO_ROOT / "horovod_tpu" / d).rglob("*.py"))]
    sources += [REPO_ROOT / "horovod_tpu" / "ops" / name
                for name in ("flash_attention.py", "losses.py")]
    assert len(sources) > 10
    readers = [str(p.relative_to(REPO_ROOT)) for p in sources
               if "environ" in p.read_text() or "getenv" in p.read_text()]
    assert readers == []
