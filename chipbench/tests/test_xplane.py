"""The trace reduction: on a trace written by hand, whose numbers can be
worked out on paper, and on one recorded on the chip."""

import gzip
import importlib
import os

import pytest
from jax.profiler import ProfileData

from chipbench import xplane

HERE = os.path.dirname(os.path.abspath(__file__))

KERNEL = ('%attn.1 = (bf16[2,20,4096,64]{3,2,1,0:T(8,128)(2,1)}, '
          'f32[2,20,4096,1]{3,2,1,0}) custom-call(bf16[2,20,4096,64]{3,2,1,0} '
          '%a, bf16[2,20,4096,64]{3,2,1,0} %b, bf16[2,20,4096,64]{3,2,1,0} '
          '%c), custom_call_target=\\"tpu_custom_call\\"')
# one step of the hand-written trace, microseconds from the program's
# start: (instruction, start, length)
STEP = [
    ("%fusion.1 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0} %p)"
     ", kind=kLoop", 0, 40),
    ("%all-reduce-start.1 = (bf16[8]{0}, bf16[8]{0:T(8)(2,1)}) "
     "all-reduce-start(bf16[8]{0} %g)", 40, 1),
    ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop", 41, 19),
    ("%all-reduce-done.1 = bf16[8]{0} all-reduce-done((bf16[8]{0}, "
     "bf16[8]{0}) %all-reduce-start.1)", 60, 10),
    # not possible on a real core, which runs one instruction at a time:
    # here so that the subtraction has something to subtract
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %r), kind=kLoop", 62, 4),
    (KERNEL, 70, 20),
    ("%psum_invariant.2 = f32[50257,1280]{1,0:T(8,128)} all-reduce("
     "f32[50257,1280]{1,0} %e), channel_id=2", 90, 5),
]


def hand_written(steps=5, period=100):
    names = [text for text, _, _ in STEP] + ["jit_step(123)"]
    meta = "".join(
        f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: '
        f'"{n}" }} }}\n' for i, n in enumerate(names))
    ops = "".join(
        f"events {{ metadata_id: {i + 1} offset_ps: "
        f"{(k * period + start) * 10 ** 6} duration_ps: {length * 10 ** 6} }}\n"
        for k in range(steps) for i, (_, start, length) in enumerate(STEP))
    modules = "".join(
        f"events {{ metadata_id: {len(names)} offset_ps: "
        f"{k * period * 10 ** 6} duration_ps: {95 * 10 ** 6} }}\n"
        for k in range(steps))
    host = "".join(
        f"events {{ metadata_id: 1 offset_ps: {(k * period + 96) * 10 ** 6} "
        f"duration_ps: {3 * 10 ** 6} stats {{ metadata_id: 1 int64_value: "
        f"{k + 1} }} }}\n" for k in range(steps))
    return ProfileData.from_text_proto(f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {modules} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {ops} }}
  {meta} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {host} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "train" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "step_num" }} }} }}''')


def test_opcode_and_kind_come_from_the_instruction_not_its_name():
    kinds = [xplane.classify(text.replace('\\"', '"')) for text, _, _ in STEP]
    assert kinds == ["compute", "collective", "compute", "collective",
                     "compute", "kernel", "collective"]
    assert xplane.opcode(STEP[-1][0]) == "all-reduce"
    assert xplane.opcode(STEP[1][0]) == "all-reduce-start"
    # a custom call that is not a Pallas kernel is compute
    assert xplane.classify('%custom-call.31 = f32[8]{0} custom-call(f32[4]{0}'
                           ' %a), custom_call_target="ConcatBitcast"') \
        == "compute"


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert xplane.gaps([(1, 2), (4, 6)], 0, 8) == [(0, 1), (2, 4), (6, 8)]
    assert xplane.clip([(0, 5), (7, 9)], 3, 8) == [(3, 5), (7, 8)]


def read(metric, trace, run=None):
    module = importlib.import_module(f"chipbench.layer_metrics.{metric}")
    return module.read(trace, run or {})


def test_hand_written_trace():
    trace = xplane.from_profile(hand_written())
    device = trace.devices[0]
    # five executions: the window runs from the second's start to the
    # last's, three whole steps of 100 us
    assert trace.window(device) == (100e3, 400e3, 3)
    # a step is busy 0-95 us (the overlapping fusion.3 adds nothing)
    assert read("step_busy_ms", trace) == pytest.approx(0.095)
    assert read("device_idle", trace) == pytest.approx(5.0)
    assert xplane.busy_and_window_seconds(trace) == pytest.approx(
        (285e-6, 300e-6))
    # collectives: start 1 + done 10 + the synchronous one 5; fusion.3
    # covers 4 us of the done
    assert read("collective_ms", trace) == pytest.approx(0.016)
    assert read("collective_exposed_ms", trace) == pytest.approx(0.012)
    assert read("flash_ms", trace) == pytest.approx(0.020)
    found = xplane.breakdown(trace)
    assert found["device_ops"][0] == ["fusion:fusion:kLoop",
                                      pytest.approx(189e-6)]
    assert ["attn:custom-call", pytest.approx(60e-6)] in found["device_ops"]
    # the only gaps are the 5 us between programs, under the host's
    # annotation of the next step
    assert found["idle_gaps"] == [[f"train#{k}", pytest.approx(5e-6)]
                                  for k in (2, 3, 4)]


def test_flash_roofline_on_the_hand_written_trace():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    shape = dict(batch=2, heads=20, seq_len=4096, head_dim=64)
    trace = xplane.from_profile(hand_written())
    got = read("flash_roofline", trace,
               {"facts": {"attention": shape}, "peak": peak})
    # one forward call a step: 85.9 GFLOP at 197 TFLOP/s is 436 us of
    # least time, over the 20 us the hand-written kernel "took"
    assert got == pytest.approx(100 * (85.899e9 / 197e12) / 20e-6, rel=1e-4)
    module = importlib.import_module("chipbench.layer_metrics.flash_roofline")
    assert module.kind(KERNEL.replace('\\"', '"')) == "fwd"
    assert module.kind("%attn.11 = bf16[2,20,4096,64]{3,2,1,0} custom-call("
                       ) == "dq"
    assert module.kind("%attn.12 = (bf16[2,20,4096,64]{3,2,1,0}, bf16[2,20,"
                       "4096,64]{3,2,1,0}) custom-call(") == "dkv"


def test_too_few_steps_is_nothing_to_read():
    trace = xplane.from_profile(hand_written(steps=2))
    assert trace.window(trace.devices[0]) is None
    assert read("step_busy_ms", trace) is None
    assert xplane.busy_and_window_seconds(trace) is None


def test_recorded_trace_of_the_four_chip_cell():
    """Chip 0 of this benchmark's own gpt2l-dp4 traced run (PR 22, seed
    409), cut to four executions of the step by ``data/cut_trace.py``,
    which also worked the expected numbers out from the raw protobuf with
    plain sums: the core runs one instruction at a time, so a step's busy
    time is the sum of its instructions' durations inside the window.
    ``ProfileData`` hands out whole nanoseconds where the protobuf has
    picoseconds, so 9,000 instructions a step read about 8 us (1e-5)
    short: hence the tolerance."""
    ns = dict(rel=5e-5)
    with gzip.open(os.path.join(HERE, "data",
                                "dp4_two_steps.xplane.pb.gz")) as f:
        trace = xplane.from_profile(ProfileData.from_serialized_xspace(
            f.read()))
    device = trace.devices[0]
    lo, hi, steps = trace.window(device)
    assert steps == 2 and hi - lo == pytest.approx(1321391198.656, rel=1e-9)
    assert read("step_busy_ms", trace) == pytest.approx(660.640598314, **ns)
    assert read("device_idle", trace) == pytest.approx(0.008324713, abs=2e-3)
    # 14 all-reduce instructions a step (12 combined bf16 ones, the norm
    # scales', and the f32 embedding's, which XLA names %psum_invariant),
    # none asynchronous, so all of their time is exposed
    collective = [o for o in device.ops if o.kind == "collective"
                  and lo <= o.start < hi]
    assert len(collective) == 28
    assert {o.opcode for o in collective} == {"all-reduce"}
    assert {o.base for o in collective} == {"all-reduce", "psum_invariant"}
    assert read("collective_ms", trace) == pytest.approx(29.181486758, **ns)
    assert read("collective_exposed_ms", trace) == pytest.approx(
        29.181486758, **ns)
    assert read("flash_ms", trace) == 0.0
    assert trace.host_steps and trace.host_steps[0][0].startswith("train#")
