"""The name-stack reader: its rule on the names JAX writes, its wire
reader against tensorflow's own parse, and the split on a trace recorded
on the chip."""

import gzip
import importlib
import os
import sys

import pytest

from chipbench import regions, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "rehearsal_three_steps.xplane.pb.gz")
# what data/cut_regions_trace.py printed when it cut the recording: ms a
# step over three steps, from tensorflow's parse and plain sums
BY_HAND = {"forward": 0.01368435, "recompute": 0.008299348666666666,
           "backward": 0.020167866, "reduce": 0.0,
           "update": 0.0006408593333333334,
           "unattributed": 0.011608410666666666}
BUSY_BY_HAND = 0.05440083466666666
LM_HEAD_BY_HAND = 0.0015190886666666668
# ProfileData gives whole nanoseconds where the file has picoseconds, and
# at the rehearsal's sizes an operation lasts a few hundred of them
WHOLE_NS = 2e-2
METRICS = {"fwd_ms": ("forward",), "recompute_ms": ("recompute",),
           "bwd_ms": ("backward",), "update_ms": ("reduce", "update"),
           "unattributed_ms": ("unattributed",)}


@pytest.mark.parametrize("op_name, expected", [
    ("jit(step)/jvp(M)/block_0/up/dot_general", "forward"),
    ("jit(step)/transpose(jvp(M))/jvp(M)/checkpoint/block_1/up/dot_general",
     "backward"),
    ("jit(step)/transpose(jvp(M))/jvp(M)/checkpoint/rematted_computation/"
     "block_1/tanh", "recompute"),
    ("jit(step)/jvp(M)/lm_head/dot_general", "forward"),
    ("jit(step)/transpose(jvp(M))/lm_head/dot_general", "backward"),
    ("jit(step)/hvt_optimizer_update/sqrt", "update"),
    ("jit(step)/cond/branch_1_fun/hvt_reduce_gradients/div", "reduce"),
    # under shard_map the gradient's reduction is part of the transpose
    ("jit(step)/jit(shmap_body)/transpose(jvp(GPT))/psum_invariant",
     "backward"),
    ("jit(step)/add", "unattributed"),       # optax.apply_updates
    ("params['embedding']", "unattributed"),
    ("", "unattributed"),
    # a fusion's names, in the order in which they are believed
    ("jit(step)/add;jit(step)/hvt_optimizer_update/mul", "update"),
    ("jit(step)/jvp(M)/tanh;jit(step)/hvt_optimizer_update/mul", "forward"),
])
def test_region_of_a_name_stack(op_name, expected):
    assert regions.region(op_name) == expected
    assert expected in regions.REGIONS
    part, _ = regions.naming_part(op_name)
    assert regions.region(part) == expected
    assert part in op_name.split(";") or (part, expected) == (
        "", "unattributed")


def test_the_scopes_are_the_packages():
    import horovod_tpu.jax as hvt_jax

    assert (regions.REDUCE_SCOPE, regions.UPDATE_SCOPE) == (
        hvt_jax.REDUCE_SCOPE, hvt_jax.UPDATE_SCOPE)


def test_wire_reader_against_tensorflows_parse():
    pytest.importorskip("tensorflow")
    from tensorflow.compiler.xla.service import hlo_pb2
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    program = hlo_pb2.HloProto()
    program.hlo_module.name = "jit_step"
    fused = program.hlo_module.computations.add(name="fused", id=7)
    for i, name in enumerate(["", "jit(step)/hvt_optimizer_update/mul",
                              "jit(step)/hvt_optimizer_update/mul",
                              "jit(step)/add"]):
        fused.instructions.add(name=f"inner.{i}", opcode="multiply",
                               id=i).metadata.op_name = name
    # a literal, so that a fixed-width field is skipped on the way
    fused.instructions[0].literal.f32s.append(1.5)
    entry = program.hlo_module.computations.add(name="main", id=300)
    entry.instructions.add(name="fusion.1", opcode="fusion", id=40,
                           called_computation_ids=[7]
                           ).metadata.op_name = "jit(step)/add"
    # a weight gradient with AdamW fused behind it: the product decides
    wgrad = program.hlo_module.computations.add(name="fused.2", id=9)
    for i, (code, name) in enumerate(
            [("multiply", "jit(step)/hvt_optimizer_update/mul")] * 3
            + [("convolution", "jit(step)/transpose(jvp(M))/up/dot_general"),
               ("add", "jit(step)/add")]):
        wgrad.instructions.add(name=f"w.{i}", opcode=code,
                               id=20 + i).metadata.op_name = name
    entry.instructions.add(name="fusion.5", opcode="fusion", id=44,
                           called_computation_ids=[9]
                           ).metadata.op_name = "jit(step)/add"
    entry.instructions.add(name="dot.2", opcode="dot", id=41
                           ).metadata.op_name = "jit(step)/jvp(M)/dot_general"
    entry.instructions.add(name="copy.3", opcode="copy", id=42)
    # not a fusion: what it calls is events of their own
    entry.instructions.add(name="call.4", opcode="call", id=43,
                           called_computation_ids=[7])
    small = hlo_pb2.HloProto()
    small.hlo_module.computations.add(id=1).instructions.add(
        name="dot.2", opcode="dot").metadata.op_name = "another program's"

    space = xplane_pb2.XSpace()
    device = space.planes.add(id=1, name="/device:TPU:0")
    device.stat_metadata[1].name = "Hlo Proto"      # not the plane read
    device.event_metadata[1].stats.add(metadata_id=1, bytes_value=b"no")
    meta = space.planes.add(id=2, name="/host:metadata")
    meta.stat_metadata[3].id = 3
    meta.stat_metadata[3].name = "Hlo Proto"
    meta.stat_metadata[4].name = "something else"
    for key, proto in ((-5, small), (-3672452744768430061, program)):
        event = meta.event_metadata[key]
        event.id = key
        event.name = "jit_step(1)"
        event.stats.add(metadata_id=4, bytes_value=b"not a program")
        event.stats.add(metadata_id=3,
                        bytes_value=proto.SerializeToString())
    raw = space.SerializeToString()

    protos = regions.hlo_protos(raw)
    assert sorted(bytes(p) for p in protos) == sorted(
        [small.SerializeToString(), program.SerializeToString()])
    names = regions.program_names(program.SerializeToString())
    assert names["fusion.1"] == ("jit(step)/hvt_optimizer_update/mul;"
                                 "jit(step)/add")
    assert names["fusion.5"] == (
        "jit(step)/transpose(jvp(M))/up/dot_general;"
        "jit(step)/hvt_optimizer_update/mul;jit(step)/add")
    assert regions.region(names["fusion.5"]) == "backward"
    assert names["dot.2"] == "jit(step)/jvp(M)/dot_general"
    assert names["copy.3"] == "" and names["call.4"] == ""
    assert names["inner.1"] == "jit(step)/hvt_optimizer_update/mul"
    assert regions.region(names["fusion.1"]) == "update"
    assert regions.hlo_protos(xplane_pb2.XSpace(
        planes=[device]).SerializeToString()) == []


def test_fields_reads_every_wire_type_and_refuses_groups():
    # 1: varint 300; 2: bytes "ab"; 3: fixed64; 4: fixed32
    message = (b"\x08\xac\x02" b"\x12\x02ab" b"\x19" + bytes(8)
               + b"\x25" + bytes(4))
    got = [(n, v if isinstance(v, int) else bytes(v))
           for n, v in regions.fields(message)]
    assert got == [(1, 300), (2, b"ab"), (3, bytes(8)), (4, bytes(4))]
    with pytest.raises(ValueError):
        list(regions.fields(b"\x0b"))           # a group's start


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """The recording where a run would have left it, and ``sys.argv`` as
    that run's."""
    folder = tmp_path / "traces" / "a-cell" / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "a-cell", "--trace", "1", "--trace-dir",
        str(tmp_path / "traces")])
    return str(path)


def test_trace_file_is_the_newest_under_the_runs_directory(
        recorded, tmp_path, monkeypatch):
    assert regions.trace_file() == recorded
    older = tmp_path / "traces" / "b-cell" / "plugins" / "profile" / "t"
    older.mkdir(parents=True)
    (older / "host.xplane.pb").write_bytes(b"")
    os.utime(older / "host.xplane.pb", (1, 1))
    assert regions.trace_file() == recorded
    assert regions.trace_file(str(tmp_path / "nowhere")) is None
    assert regions.trace_file(str(tmp_path)) is None
    # `--trace 1` is not an abbreviation of `--trace-dir`
    monkeypatch.setattr(sys, "argv", ["run.py", "--trace", "1"])
    assert regions.trace_file() != "1"


def test_the_split_of_the_recorded_trace(recorded):
    trace = xplane.load(recorded)
    names = regions.name_stacks(recorded)
    split = regions.region_ms(trace, names)
    assert split == pytest.approx(BY_HAND, rel=WHOLE_NS)
    # three layers under remat: something ran again, and AdamW's fusions,
    # whose own name is apply_updates' bare add, are the update's
    assert split["recompute"] > 0 and split["update"] > 0
    assert names["multiply_add_fusion.42"].endswith(";jit(step)/add")
    assert regions.region(names["multiply_add_fusion.42"]) == "update"
    # XLA Ops is one serial line: the regions (and the collectives, of
    # which one chip has none) are the busy time
    busy = importlib.import_module(
        "chipbench.layer_metrics.step_busy_ms").read(trace, {})
    collective = importlib.import_module(
        "chipbench.layer_metrics.collective_ms").read(trace, {})
    assert collective == 0
    assert busy == pytest.approx(BUSY_BY_HAND, rel=WHOLE_NS)
    assert sum(split.values()) + collective == pytest.approx(busy, rel=1e-9)
    # the builder's look: every label of a region adds up to the region
    # (none has more than eight here but forward, recompute and backward),
    # and XLA has put AdamW inside some of the backward pass's fusions
    seen = regions.look(trace, names, count=1000)
    for name, ms in split.items():
        assert sum(v for _, v in seen["top_ops"][name]) == pytest.approx(ms)
    inside = seen["with_update_inside_ms"]
    assert inside["update"] == pytest.approx(split["update"])
    assert 0 < inside["backward"] < split["backward"]
    assert inside["forward"] == inside["recompute"] == 0


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_reader_reads_its_regions_or_nothing(metric, recorded, tmp_path,
                                               monkeypatch, capsys):
    module = importlib.import_module(f"chipbench.layer_metrics.{metric}")
    trace = xplane.load(recorded)
    assert module.read(trace, {}) == pytest.approx(
        sum(BY_HAND[r] for r in METRICS[metric]), rel=WHOLE_NS)
    # no device plane, as on the CPU
    assert module.read(None, {}) is None
    # a program without the package's scopes (the parent's, or a cached
    # one): the regions JAX names are read, the other two are left out
    unnamed = {k: v.replace(regions.UPDATE_SCOPE, "optimizer")
               for k, v in regions.name_stacks(recorded).items()}
    monkeypatch.setattr(regions, "name_stacks", lambda path: unnamed)
    if metric in ("update_ms", "unattributed_ms"):
        assert module.read(trace, {}) is None
        assert regions.UPDATE_SCOPE in capsys.readouterr().out
    else:
        assert module.read(trace, {}) == pytest.approx(
            BY_HAND[METRICS[metric][0]], rel=WHOLE_NS)
    # a trace without a program, and no trace file at all
    monkeypatch.setattr(regions, "name_stacks", lambda path: None)
    assert module.read(trace, {}) is None
    monkeypatch.setattr(sys, "argv", ["run.py", "--trace-dir",
                                      str(tmp_path / "nowhere")])
    assert module.read(trace, {}) is None


def test_lm_head_is_read_by_its_scope_across_regions(recorded, monkeypatch):
    module = importlib.import_module("chipbench.layer_metrics.lm_head_ms")
    trace = xplane.load(recorded)
    got = module.read(trace, {})
    assert got == pytest.approx(LM_HEAD_BY_HAND, rel=WHOLE_NS)
    # forward and backward both: more than either region's share of it
    names = regions.name_stacks(recorded)
    split = regions.region_ms(trace, names, scope="/lm_head/")
    assert 0 < split["forward"] < got and 0 < split["backward"] < got
    assert split["recompute"] == split["update"] == 0   # outside remat
    # a program that does not name the projection: left out, not 0
    unnamed = {k: v.replace("/lm_head/", "/") for k, v in names.items()}
    monkeypatch.setattr(regions, "name_stacks", lambda path: unnamed)
    assert module.read(trace, {}) is None


def test_collective_bytes_and_calls_of_the_recorded_dp4_trace(tmp_path):
    # GPT-2 large, every gradient reduced once a step: the matrices of 36
    # blocks in bf16, the 73 norm scales and the embedding in f32
    # (PERF.md, PR 22, counted by hand then: 1.42 GB + 0.26 GB in 14)
    path = tmp_path / "dp4.xplane.pb"
    with gzip.open(os.path.join(HERE, "data",
                                "dp4_two_steps.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    trace = xplane.load(str(path))
    mb = importlib.import_module("chipbench.layer_metrics.collective_mb")
    calls = importlib.import_module(
        "chipbench.layer_metrics.collective_calls")
    d, ff, vocab, layers = 1280, 5120, 50257, 36
    expected = (layers * (4 * d * d + 2 * d * ff) * 2
                + (2 * layers + 1) * d * 4 + vocab * d * 4
                + 4)            # the step's pmean of the loss
    assert round(mb.read(trace, {}) * 1e6) == expected
    assert calls.read(trace, {}) == 14
    assert mb.read(None, {}) is None and calls.read(None, {}) is None


@pytest.mark.parametrize("text, expected", [
    ("%n = (bf16[8,4]{1,0:T(8,128)(2,1)S(1)}, /*index=1*/f32[2]{0}) "
     "all-reduce(bf16[8,4]{1,0} %a, f32[2]{0} %b), channel_id=1", 72),
    ("%d = bf16[8]{0} all-reduce-done((bf16[8]{0}, bf16[8]{0}) %s.1)", 16),
    ("%p = pred[] all-reduce(pred[] %x)", 1),
    ("%g = f32[4,0]{1,0} all-gather(f32[1,0]{1,0} %x)", 0),
    ("%s = f32[] all-reduce(f32[] %x), to_apply=%add", 4),
])
def test_output_bytes_from_an_instructions_text(text, expected):
    mb = importlib.import_module("chipbench.layer_metrics.collective_mb")
    assert mb.output_bytes(text) == expected
