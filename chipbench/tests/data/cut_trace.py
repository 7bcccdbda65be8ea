"""How ``dp4_two_steps.xplane.pb.gz`` was made (not run by any test).

    python chipbench/tests/data/cut_trace.py <recorded.xplane.pb> <out.gz> \
        <first execution kept> <executions kept>

Cuts a recorded trace to chip 0's ``XLA Modules`` and ``XLA Ops`` lines
over a few executions of the step program, plus the host's step
annotations, drops every stat, and gzips it. Then prints the numbers
``test_xplane.py`` expects, worked out from the raw protobuf with plain
sums (the core runs one instruction at a time, so no interval arithmetic
is needed): nothing of ``chipbench/xplane.py`` is used. Needs
tensorflow's copy of the xplane protobuf, which ``xplane.py`` does not.
"""
import gzip, sys, re
from tensorflow.tsl.profiler.protobuf import xplane_pb2
src, dst, first, count = sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4])
space = xplane_pb2.XSpace(); space.ParseFromString(open(src, "rb").read())
out = xplane_pb2.XSpace()
dev = next(p for p in space.planes if p.name == "/device:TPU:0")
def abs_ps(line, e): return line.timestamp_ns * 1000 + e.offset_ps
mods = next(l for l in dev.lines if l.name == "XLA Modules")
mev = sorted(mods.events, key=lambda e: abs_ps(mods, e))[first:first + count]
lo = abs_ps(mods, mev[0]); hi = abs_ps(mods, mev[-1]) + mev[-1].duration_ps
new = out.planes.add(); new.id = dev.id; new.name = dev.name
used = set()
for line in dev.lines:
    if line.name not in ("XLA Modules", "XLA Ops"): continue
    nl = new.lines.add(); nl.id = line.id; nl.name = line.name; nl.timestamp_ns = line.timestamp_ns
    for e in line.events:
        if lo <= abs_ps(line, e) <= hi:
            ne = nl.events.add(); ne.metadata_id = e.metadata_id; ne.offset_ps = e.offset_ps; ne.duration_ps = e.duration_ps
            used.add(e.metadata_id)
for k in used:
    m = new.event_metadata[k]; m.id = dev.event_metadata[k].id; m.name = dev.event_metadata[k].name
host = next(p for p in space.planes if p.name == "/host:CPU")
nh = out.planes.add(); nh.id = host.id; nh.name = host.name
step_stat = [k for k, v in host.stat_metadata.items() if v.name == "step_num"]
for k in step_stat:
    nh.stat_metadata[k].id = k; nh.stat_metadata[k].name = "step_num"
for line in host.lines:
    keep = [e for e in line.events if any(s.metadata_id in step_stat for s in e.stats)]
    if not keep: continue
    nl = nh.lines.add(); nl.id = line.id; nl.name = line.name; nl.timestamp_ns = line.timestamp_ns
    for e in keep:
        ne = nl.events.add(); ne.metadata_id = e.metadata_id; ne.offset_ps = e.offset_ps; ne.duration_ps = e.duration_ps
        for s in e.stats:
            if s.metadata_id in step_stat:
                ns = ne.stats.add(); ns.CopyFrom(s)
        nh.event_metadata[e.metadata_id].id = e.metadata_id
        nh.event_metadata[e.metadata_id].name = host.event_metadata[e.metadata_id].name
raw = out.SerializeToString()
with gzip.open(dst, "wb", compresslevel=9) as f: f.write(raw)
import os
print("raw bytes", len(raw), "gz bytes", os.path.getsize(dst))
# ---- by hand: window = start of 2nd kept module to start of last kept module; ops are sequential on the line
w_lo = abs_ps(mods, mev[1]); w_hi = abs_ps(mods, mev[-1]); steps = count - 2
ops = next(l for l in dev.lines if l.name == "XLA Ops")
busy = coll = kern = 0; n_coll = n_kern = 0; prev_end = None; overlaps = 0
for e in sorted(ops.events, key=lambda e: abs_ps(ops, e)):
    a = abs_ps(ops, e); b = a + e.duration_ps
    a2, b2 = max(a, w_lo), min(b, w_hi)
    if b2 <= a2: continue
    if prev_end is not None and a2 < prev_end: overlaps += 1
    prev_end = b2
    busy += b2 - a2
    name = dev.event_metadata[e.metadata_id].name
    if re.search(r" (all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(-start|-done)?\(", name):
        coll += b2 - a2; n_coll += 1
    if 'custom_call_target="tpu_custom_call"' in name:
        kern += b2 - a2; n_kern += 1
print("window_ps", w_hi - w_lo, "steps", steps, "overlapping events", overlaps)
print("busy_ms_per_step", busy / steps / 1e9, "idle_%", 100 * (1 - busy / (w_hi - w_lo)))
print("collective_ms_per_step", coll / steps / 1e9, "n", n_coll, "kernel_ms_per_step", kern / steps / 1e9, "n", n_kern)
