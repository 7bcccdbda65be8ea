"""``chipbench/kernel_calls.py`` and the nine readers built on it, on a
trace made by hand: two steps in the window; a flash forward whose first
call is longer than its recomputed one; a round loop whose body ran twice
a step; a ``while`` and a ``conditional`` event spanning their bodies'; a
collective; an event the window cuts."""

import importlib

import pytest

from chipbench import kernel_calls, regions, xplane

PALLAS = ', custom_call_target="tpu_custom_call"'
FWD = "jit(step)/jvp(GPT)/block_0/attn/"
AGAIN = ("jit(step)/transpose(jvp(GPT))/jvp(GPT)/checkpoint/"
         "rematted_computation/block_0/attn/")
BWD = "jit(step)/transpose(jvp(GPT))/block_0/attn/"
MOE = "jit(step)/jvp(GPT)/block_1/moe/while/body/moe_experts/"
# (instruction, opcode, Pallas, op_name, [(start, length) within a step])
PROGRAM = [
    ("fusion.9", "fusion", False, FWD + "attn_proj/q/dot_general", [(0, 40)]),
    ("fusion.10", "fusion", False, FWD + "attn_norm/q_norm/mul", [(40, 8)]),
    ("fusion.11", "fusion", False, FWD + "attn_rope/mul", [(48, 6)]),
    ("copy.14", "copy", False, FWD + "attn_core/transpose", [(54, 4)]),
    ("hvt_flash_fwd.1", "custom-call", True, FWD + "attn_core/hvt_flash_fwd",
     [(58, 30)]),
    ("fusion.12", "fusion", False, FWD + "attn_gate/logistic", [(88, 5)]),
    ("fusion.13", "fusion", False, FWD + "attn_out_proj/o/dot_general",
     [(93, 20)]),
    # a layer's round loop: the container spans two trips of its body
    ("while.4", "while", False, MOE + "while", [(120, 60)]),
    ("gmm.5", "custom-call", True, MOE + "gmm", [(120, 10), (150, 10)]),
    ("gmm.6", "custom-call", True, MOE + "gmm", [(130, 12), (160, 12)]),
    ("hvt_moe_kth.16", "custom-call", True,
     "jit(step)/jvp(GPT)/block_1/moe/moe_route/hvt_moe_kth", [(180, 7)]),
    ("conditional.8", "conditional", False, "jit(step)/cond", [(190, 300)]),
    ("hvt_flash_fwd.2", "custom-call", True,
     AGAIN + "attn_core/hvt_flash_fwd", [(200, 20)]),
    ("fusion.17", "fusion", False, AGAIN + "attn_rope/mul", [(220, 6)]),
    ("hvt_flash_bwd.3", "custom-call", True, BWD + "attn_core/hvt_flash_bwd",
     [(230, 50)]),
    ("tgmm.7", "custom-call", True,
     "jit(step)/transpose(jvp(GPT))/block_1/moe/moe_experts/tgmm",
     [(300, 15)]),
    ("all-reduce.15", "all-reduce", False, BWD + "attn_core/psum",
     [(320, 100)]),
    ("fusion.18", "fusion", False, "jit(step)/hvt_optimizer_update/mul",
     [(900, 200)]),     # runs into the next step: the window's end cuts it
]
STEP, STEPS = 1000, 4   # the window holds the second and the third


@pytest.fixture
def made(monkeypatch):
    ops, names = [], {}
    for name, opcode, pallas, op_name, runs in PROGRAM:
        text = f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %x)" + (
            PALLAS if pallas else "")
        names[name] = op_name
        for step in range(STEPS):
            ops += [xplane.Op(text, step * STEP + at, step * STEP + at + ns,
                              xplane.classify(text)) for at, ns in runs]
    modules = [("jit_step", i * STEP, i * STEP + 990) for i in range(STEPS)]
    trace = xplane.Trace([xplane.Device(
        "/device:TPU:0", sorted(ops, key=lambda o: o.start), modules)], [])
    assert trace.window(trace.devices[0]) == (1000, 3000, 2)
    monkeypatch.setattr(regions, "trace_file", lambda *a: "somewhere")
    monkeypatch.setattr(regions, "name_stacks", lambda path: names)
    return trace, names


def test_walk_leaves_out_containers_and_collectives_and_clips(made):
    trace, names = made
    found = kernel_calls.walk(trace, names)
    assert found.steps == 2
    bases = [e.base for e in found.events]
    assert not {"while", "conditional", "all-reduce"} & set(bases)
    # the second and third steps' events, and the 100 ns of the first
    # step's update that the window's start leaves
    per_step = sum(len(runs) for name, *_, runs in PROGRAM
                   if name.split(".")[0] not in ("while", "conditional",
                                                 "all-reduce"))
    assert len(found.events) == 2 * per_step + 1
    cut = [e.ns for e in found.events if e.name == "fusion.18"]
    assert cut == [100, 200, 100]
    by_name = {e.name: e for e in found.events}
    assert by_name["hvt_flash_fwd.1"].region == "forward"
    assert by_name["hvt_flash_fwd.2"].region == "recompute"
    assert by_name["hvt_flash_bwd.3"].region == "backward"
    assert by_name["fusion.18"].region == "update"
    assert by_name["gmm.5"].kernel and not by_name["fusion.9"].kernel
    assert by_name["gmm.5"].base == by_name["gmm.6"].base == "gmm"
    assert kernel_calls.calls_a_step(found) == {
        "hvt_flash_fwd": 2, "hvt_flash_bwd": 1, "gmm": 4, "tgmm": 1,
        "hvt_moe_kth": 1}


# ns a step, or ns a call, or executions a step of an instruction
EXPECTED = {
    "attn_ms": 40 + 8 + 6 + 4 + 30 + 5 + 20 + 20 + 6 + 50,
    "attn_core_ms": 4 + 30 + 20 + 50,
    "attn_elementwise_ms": 8 + 6 + 5 + 6,
    "flash_fwd_ms": 30 + 20,
    "flash_bwd_ms": 50,
    "flash_fwd_first_call_ms": 30,
    "flash_fwd_again_call_ms": 20,
    "grouped_ms": 2 * 10 + 2 * 12 + 15,
}


def _reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}")


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_reader_reads_its_events(name, made):
    assert _reader(name).read(made[0], {}) == pytest.approx(
        EXPECTED[name] / 1e6)


def test_rounds_are_the_trips_of_the_loop_the_kernel_is_in(made, capsys):
    trace, names = made
    assert _reader("moe_rounds").read(trace, {}) == pytest.approx(2.0)
    assert "2 gmm instructions; most for one 2.0" in capsys.readouterr().out
    # a third trip of one layer's loop in one step of the two
    device = trace.devices[0]
    extra = next(o for o in device.ops if o.name == "gmm.6")
    device.ops.append(xplane.Op(extra.text, 1170, 1175, "kernel"))
    assert _reader("moe_rounds").read(trace, {}) == pytest.approx(9 / 4)
    assert "most for one 2.5" in capsys.readouterr().out


def test_nine_readers_walk_the_window_once_a_run(made, monkeypatch):
    trace, _ = made
    walks = []
    walk = kernel_calls.walk
    monkeypatch.setattr(kernel_calls, "walk",
                        lambda *a: walks.append(a) or walk(*a))
    run = {"facts": {}}     # what run.py hands every reader of a run
    for name in list(EXPECTED) + ["moe_rounds"]:
        assert _reader(name).read(trace, run) is not None
    assert len(walks) == 1 and set(run) == {"facts", kernel_calls.KEPT}
    # another run keeps its own: without the scopes' names only the
    # readers that go by a kernel's name find something
    monkeypatch.setattr(regions, "name_stacks", lambda path: {"op": "x"})
    other = {}
    assert _reader("attn_ms").read(trace, other) is None
    assert _reader("flash_fwd_ms").read(trace, other) == pytest.approx(5e-5)
    assert len(walks) == 2


@pytest.mark.parametrize("name", sorted(EXPECTED) + ["moe_rounds"])
def test_a_reader_that_finds_nothing_returns_none(name, made, monkeypatch):
    trace, names = made
    read = _reader(name).read
    # no device plane, as on the CPU
    assert read(None, {}) is None
    # the parent's program: no attn_* scope; and a program whose kernels
    # have other names
    monkeypatch.setattr(regions, "name_stacks", lambda path: {
        k: v.replace("/attn_", "/") for k, v in names.items()})
    assert (read(trace, {}) is None) == name.startswith("attn_")
    for op in trace.devices[0].ops:
        object.__setattr__(op, "text", op.text.replace(
            "%hvt_flash_", "%attn_").replace("gmm.", "dot."))
    assert read(trace, {}) is None
    # a trace without a program, no window, and no trace file at all
    monkeypatch.setattr(regions, "name_stacks", lambda path: None)
    assert read(trace, {}) is None
    monkeypatch.setattr(regions, "name_stacks", lambda path: names)
    assert read(xplane.Trace([xplane.Device("/device:TPU:0", [], [])], []),
                {}) is None
    monkeypatch.setattr(regions, "trace_file", lambda *a: None)
    assert read(trace, {}) is None
