"""How ``rehearsal_three_steps.xplane.pb.gz`` was made (not run by any
test).

    python chipbench/tests/data/cut_regions_trace.py <recorded.xplane.pb> \
        <out.gz> <first execution kept> <executions kept>

The recording is PR 24's first chip call: ``python3 -m chipbench.run
--workload gpt2l-s1024 --seed 1 --seconds 5 --trace 1 --rehearse`` on a
v5e (three layers of width 64, ``remat``, AdamW through
``hvt.DistributedOptimizer``). Cut as ``cut_trace.py`` cuts: chip 0's
``XLA Modules`` and ``XLA Ops`` lines over a few executions, every stat
of an event dropped. Kept besides: plane ``/host:metadata`` with each
program's ``Hlo Proto`` stat, the program cut down to what
``chipbench/regions.py`` reads (computations' ids; instructions' names,
opcodes, ``metadata.op_name`` and called computations). Then prints the
split ``test_regions.py`` expects, worked out from tensorflow's parse of
the protobufs with plain sums: nothing of ``chipbench/`` is used.
"""
import gzip, os, sys
from tensorflow.compiler.xla.service import hlo_pb2
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
meta = next(p for p in space.planes if p.name == "/host:metadata")
nm = out.planes.add(); nm.id = meta.id; nm.name = meta.name
for k, v in meta.stat_metadata.items():
    nm.stat_metadata[k].id = v.id; nm.stat_metadata[k].name = v.name
programs = []
for k, md in meta.event_metadata.items():
    full = hlo_pb2.HloProto(); full.ParseFromString(md.stats[0].bytes_value)
    slim = hlo_pb2.HloProto(); slim.hlo_module.name = full.hlo_module.name
    for c in full.hlo_module.computations:
        nc = slim.hlo_module.computations.add(); nc.name = c.name; nc.id = c.id
        for i in c.instructions:
            ni = nc.instructions.add(); ni.name = i.name; ni.opcode = i.opcode; ni.id = i.id
            ni.metadata.op_name = i.metadata.op_name
            ni.called_computation_ids.extend(i.called_computation_ids)
    n = nm.event_metadata[k]; n.id = md.id; n.name = md.name
    s = n.stats.add(); s.metadata_id = md.stats[0].metadata_id; s.bytes_value = slim.SerializeToString()
    programs.append(slim.hlo_module)
raw = out.SerializeToString()
with gzip.open(dst, "wb", compresslevel=9) as f: f.write(raw)
print("raw bytes", len(raw), "gz bytes", os.path.getsize(dst))
# ---- by hand: window = start of 2nd kept module to start of last kept module; ops are sequential on the line
import collections
op_name, inside = {}, {}
for module in programs:
    comps = {c.id: c for c in module.computations}
    for c in module.computations:
        for i in c.instructions:
            op_name[i.name] = i.metadata.op_name
            inside[i.name] = [(j.opcode, j.metadata.op_name) for cid in i.called_computation_ids for j in comps[cid].instructions] if i.opcode == "fusion" else []
def region_of(n):
    if "rematted_computation" in n: return "recompute"
    if "transpose(jvp(" in n: return "backward"
    if "hvt_reduce_gradients" in n: return "reduce"
    if "hvt_optimizer_update" in n: return "update"
    if "jvp(" in n: return "forward"
def deciding_name(short):
    """A fusion: the matrix multiplication or convolution it holds, else the first name of the region most of its instructions name, else its own."""
    heavy = [n for code, n in inside[short] if code in ("dot", "convolution") and region_of(n)]
    if heavy: return heavy[0]
    votes = collections.Counter(region_of(n) for _, n in inside[short] if region_of(n))
    if votes:
        most = votes.most_common(1)[0][0]
        return next(n for _, n in inside[short] if region_of(n) == most)
    return op_name[short]
w_lo = abs_ps(mods, mev[1]); w_hi = abs_ps(mods, mev[-1]); steps = count - 2
ops = next(l for l in dev.lines if l.name == "XLA Ops")
split, busy, lm_head = {}, 0, 0
for e in ops.events:
    a = abs_ps(ops, e); b = a + e.duration_ps
    a2, b2 = max(a, w_lo), min(b, w_hi)
    if b2 <= a2: continue
    busy += b2 - a2
    short = dev.event_metadata[e.metadata_id].name.split(" = ")[0].lstrip("%")
    named = deciding_name(short)
    r = region_of(named) or "unattributed"
    split[r] = split.get(r, 0) + b2 - a2
    if "/lm_head/" in named: lm_head += b2 - a2
print("steps", steps, "busy_ms_per_step", busy / steps / 1e9)
print({r: v / steps / 1e9 for r, v in split.items()}, "lm_head", lm_head / steps / 1e9)
