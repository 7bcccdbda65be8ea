"""Process topology and lifecycle — the TPU-native analog of
``HorovodBasics`` (reference ``horovod/common/basics.py:22-258``).

Topology model
--------------
Horovod runs one process per accelerator; ``rank``/``size`` count processes.
A TPU pod slice is driven by one process per **host**, each owning
``local_device_count`` chips, and the training step is one SPMD program over
all chips. The Horovod notions map as:

===============  ======================================  =====================
Horovod          horovod_tpu                             reference anchor
===============  ======================================  =====================
``size``         total chip slots ``jax.device_count()``  ``basics.py:142``
``local_size``   chips on this host                       ``basics.py:166``
``rank``         global index of this host's first chip   ``basics.py:130``
``local_rank``   0 (the process drives slot
                 ``rank()..rank()+local_size()``)         ``basics.py:154``
``cross_size``   number of hosts                          ``basics.py:190``
``cross_rank``   host index                               ``basics.py:178``
===============  ======================================  =====================

``rank() == 0`` is true exactly on the coordinator host, so the ubiquitous
``if hvd.rank() == 0:`` idiom keeps working. Per-chip ranks exist *inside*
the compiled program (``jax.lax.axis_index``); see
``horovod_tpu/ops/collective_ops.py``.
"""

from __future__ import annotations

import atexit
import os
import threading

_lock = threading.Lock()
_initialized = False
_started_jax_distributed = False
_debugz_stop = None  # Event for the /debugz pusher thread, when running


def _jax():
    import jax

    return jax


def init(comm=None, process_sets=None):
    """Initialize horovod_tpu.

    Reference call stack: ``hvd.init()`` → ``InitializeHorovodOnce``
    (``operations.cc:649``) spawns the background engine thread and runs the
    controller rendezvous. TPU-natively:

    1. If launched multi-host (env from the ``hvtrun`` launcher or a
       pre-configured ``jax.distributed`` cluster), join the cluster via
       ``jax.distributed.initialize`` — this is the DCN control-plane
       rendezvous, the analog of Gloo's HTTP-store rendezvous
       (``gloo/gloo_context.cc``).
    2. Build the default global device mesh (ICI data plane).
    3. Start the eager-path C++ engine lazily on first eager collective.

    ``comm`` is accepted for API parity (the reference takes an MPI comm or
    rank lists); passing a non-default value raises, since process placement
    on TPU is owned by the launcher.
    """
    global _initialized
    if comm not in (None, 0):
        raise ValueError(
            "horovod_tpu.init(comm=...) is not supported: process "
            "placement on TPU is owned by the launcher (hvtrun)")
    with _lock:
        if _initialized:
            return
        from horovod_tpu.metrics import startup as _startup

        with _startup.span("init"):
            _init_locked(process_sets)
            # last, so that no stage span lies inside ``init``: the
            # phases and JAX's stages tile a start without overlap
            _startup.listen()
        _initialized = True


def _init_locked(process_sets):
    from horovod_tpu.metrics.startup import span

    jax = _jax()

    if os.environ.get("HVT_FROM_MPI"):
        # mpirun/jsrun placed us: derive slot identity from the MPI
        # launcher's env (OMPI_COMM_WORLD_RANK etc.)
        from horovod_tpu.runner.mpi_run import env_from_mpi

        os.environ.update(env_from_mpi())

    coordinator = os.environ.get("HVT_COORDINATOR_ADDR")
    nprocs = os.environ.get("HVT_NUM_PROCESSES")
    procid = os.environ.get("HVT_PROCESS_ID")
    if coordinator and nprocs and int(nprocs) > 1:
        global _started_jax_distributed
        with span("distributed_join"):
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=int(nprocs),
                process_id=int(procid) if procid is not None else None,
            )
        _started_jax_distributed = True

    # CPU engine mode (hvtrun -np N for the eager/torch path): bring up
    # the C++ core — control star + TCP data mesh + background thread
    # (the analog of the reference's InitializeHorovodOnce spawning
    # BackgroundThreadLoop, operations.cc:649,688).
    master = os.environ.get("HVT_MASTER_ADDR")
    if master and nprocs and int(nprocs) > 1:
        from horovod_tpu.engine import native as _native

        if not _native.available():
            raise RuntimeError(
                "hvtrun multi-process launch requires the C++ engine; "
                "build it with `make -C horovod_tpu/csrc`")
        with span("engine"):
            _native.init_engine(
                rank=int(procid or 0), size=int(nprocs),
                master_addr=master,
                master_port=int(os.environ.get("HVT_MASTER_PORT", "29510")),
                cycle_ms=int(os.environ.get("HVT_CYCLE_TIME_MS", "2")))

    metrics_port = os.environ.get("HVT_METRICS_PORT")
    shard_base = os.environ.get("HVT_TIMELINE_SHARD")
    # HVT_DIAG_ADDR: the static launcher's KV server (--timeline);
    # HVT_RENDEZVOUS_ADDR: the elastic rendezvous (same surface).
    # The split exists because the latter is the "elastic launch"
    # marker that elastic/run.py and preemption.py key off.
    rdv_addr = (os.environ.get("HVT_DIAG_ADDR")
                or os.environ.get("HVT_RENDEZVOUS_ADDR"))
    if metrics_port is not None or shard_base:
        with span("endpoints"):
            _start_endpoints(metrics_port, shard_base, rdv_addr,
                             int(procid or 0))

    # Background /debugz reporter: periodically push this worker's
    # diagnostics() snapshot to the rendezvous KV so the launcher's
    # GET /debugz names stalled tensors without touching workers.
    if rdv_addr:
        global _debugz_stop
        _debugz_stop = threading.Event()
        threading.Thread(
            target=_debugz_push_loop,
            args=(rdv_addr, int(procid or 0), _debugz_stop),
            daemon=True).start()

    # Materialize the device list once; this is the global communicator.
    from horovod_tpu.parallel import mesh as _mesh

    _mesh.build_global_mesh()

    from horovod_tpu.common import process_sets as _ps

    with span("process_sets"):
        _ps._init_global_process_set()
        if process_sets:
            for ps in process_sets:
                _ps.add_process_set(ps)


def _start_endpoints(metrics_port, shard_base, rdv_addr, my_rank):
    # Telemetry endpoint (hvtrun --metrics-port → HVT_METRICS_PORT):
    # every worker serves GET /metrics at base_port + process_rank so
    # co-hosted workers never collide; port 0 binds ephemerally.
    if metrics_port is not None:
        from horovod_tpu import metrics as _metrics

        base = int(metrics_port)
        offset = my_rank if base else 0
        bound = _metrics.serve(base + offset)
        if os.environ.get("HVT_VERBOSE"):
            print(f"[hvt] metrics endpoint on :{bound}/metrics")

    # Flight recorder (hvtrun --timeline → HVT_TIMELINE_SHARD): every
    # worker records a per-rank chrome-trace shard, clock-aligned to
    # the rendezvous server and uploaded there at teardown so the
    # launcher can merge all ranks into one loadable trace.
    if shard_base:
        from horovod_tpu.utils import timeline as _tl

        if rdv_addr:
            try:
                _tl.set_clock_offset_us(
                    _tl.measure_clock_offset_us(rdv_addr))
            except Exception:
                pass  # unaligned shards still merge, just skewed
        # xla_profiler off: every gang member arming a PJRT session
        # would fight over the one-session limit; opt back in with
        # HVT_TIMELINE_XLA=1 via start_timeline on the rank you want
        _tl.start(f"{shard_base}.rank{my_rank}",
                  mark_cycles=os.environ.get(
                      "HVT_TIMELINE_MARK_CYCLES", "0") != "0",
                  xla_profiler=False, pid=my_rank,
                  upload_addr=rdv_addr)


def shutdown():
    """Tear down the engine and (if we started it) the jax.distributed client.

    Reference: ``horovod_shutdown`` (``operations.cc:728``) joins the
    background thread and finalizes pending tensors with SHUT_DOWN_ERROR.
    """
    global _initialized, _started_jax_distributed, _debugz_stop
    with _lock:
        if not _initialized:
            return
        if _debugz_stop is not None:
            _debugz_stop.set()
            _debugz_stop = None
        from horovod_tpu.engine import api as _engine_api

        _engine_api.shutdown_if_running()
        # elastic rounds re-init through here: auto-name counters must
        # restart from the same point on survivors and fresh workers
        # alike, or their anonymous collectives never pair (see
        # engine/api.reset_auto_names)
        _engine_api.reset_auto_names()
        # after the engine: its teardown records the final DONE/abort
        # events, which the timeline's last drain must still capture
        from horovod_tpu.utils import timeline as _tl

        _tl.stop()
        if _started_jax_distributed:
            try:
                _jax().distributed.shutdown()
            except Exception:
                pass
            _started_jax_distributed = False
        from horovod_tpu.parallel import mesh as _mesh

        _mesh._reset()
        from horovod_tpu.common import process_sets as _ps

        _ps._reset()
        from horovod_tpu import metrics as _metrics
        from horovod_tpu.metrics import startup as _startup

        _startup.stop_listening()
        if os.environ.get("HVT_VERBOSE"):
            print(_startup.format_report(_startup.report()), flush=True)
        _metrics.stop_server()
        _initialized = False


atexit.register(shutdown)


def is_initialized():
    """Parity with ``basics.py:212`` (is_initialized)."""
    return _initialized


def _ensure_init():
    if not _initialized:
        raise ValueError(
            "horovod_tpu has not been initialized; run hvt.init() first.")


def _engine():
    from horovod_tpu.engine import native

    return native if native.engine_running() else None


def size() -> int:
    """Horovod world size: engine processes in CPU engine mode, chip slots
    in TPU/SPMD mode."""
    _ensure_init()
    eng = _engine()
    if eng is not None:
        return eng.engine_size()
    return _jax().device_count()


def local_size() -> int:
    """Engine mode: processes on this host (launcher env); TPU mode: chips
    driven by this process."""
    _ensure_init()
    if _engine() is not None:
        return int(os.environ.get("HVT_LOCAL_SIZE", "1"))
    return _jax().local_device_count()


def rank() -> int:
    """Global slot index of this process's first chip.

    ``rank() == 0`` exactly on the coordinator process. Per-chip ranks live
    inside the compiled program (``lax.axis_index``). Engine mode: the
    process rank assigned by the launcher.
    """
    _ensure_init()
    eng = _engine()
    if eng is not None:
        return eng.engine_rank()
    jax = _jax()
    local = jax.local_devices()
    if not local:
        return 0
    # Slot index = POSITION of this process's first device in the global
    # id order, not the raw id: TPU ids are contiguous slot numbers, but
    # multi-process CPU/GPU backends offset ids per process (e.g. CPU
    # ids jump by 131072 per process), so counting smaller ids is the
    # platform-independent form.
    mine = min(d.id for d in local)
    return sum(1 for d in jax.devices() if d.id < mine)


def local_rank() -> int:
    """Index of this process among processes on the same physical host.

    One process drives all chips of a host, so this is 0 unless several
    horovod_tpu processes share a host (supported for CPU testing, where the
    launcher sets HVT_LOCAL_PROCESS_ID)."""
    _ensure_init()
    return int(os.environ.get("HVT_LOCAL_PROCESS_ID", "0"))


def cross_rank() -> int:
    """Host index (reference CROSS communicator rank, ``common.h:115-119``)."""
    _ensure_init()
    if _engine() is not None:
        return int(os.environ.get("HVT_CROSS_RANK", "0"))
    return _jax().process_index()


def cross_size() -> int:
    """Number of hosts."""
    _ensure_init()
    if _engine() is not None:
        return int(os.environ.get("HVT_CROSS_SIZE", "1"))
    return _jax().process_count()


def process_rank() -> int:
    """This Python process's index (== cross_rank on TPU pods)."""
    _ensure_init()
    eng = _engine()
    if eng is not None:
        return eng.engine_rank()
    return _jax().process_index()


def process_size() -> int:
    """Number of Python processes."""
    _ensure_init()
    eng = _engine()
    if eng is not None:
        return eng.engine_size()
    return _jax().process_count()


def is_homogeneous() -> bool:
    """True when every host drives the same number of chips
    (reference ``mpi_controller.cc:51-63`` homogeneity detection)."""
    _ensure_init()
    jax = _jax()
    counts = {}
    for d in jax.devices():
        counts[d.process_index] = counts.get(d.process_index, 0) + 1
    return len(set(counts.values())) <= 1


# --- build-info surface (reference basics.py:216-258) -----------------------
# These exist so reference scripts that branch on them keep working; the TPU
# build has exactly one data plane (XLA over ICI/DCN) plus the C++ TCP engine
# for eager/CPU collectives (the Gloo-equivalent).

def nccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def mpi_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def mpi_threads_supported() -> bool:
    return False


def gloo_built() -> bool:
    """The C++ TCP ring engine is the Gloo equivalent; True when its shared
    library is available."""
    from horovod_tpu.engine import api as _engine_api

    return _engine_api.library_available()


def gloo_enabled() -> bool:
    return gloo_built()


def xla_built() -> bool:
    """TPU-native addition: the XLA/ICI data plane is always built in."""
    return True


# --- engine telemetry bridge (horovod_tpu.metrics) --------------------------

def poll_engine_stats(registry=None):
    """Pull the C++ engine's atomic stats block (``hvt_engine_stats``,
    ``csrc/c_api.cc``) into metric counters/gauges.

    Registered as a collector on the default registry
    (``horovod_tpu.metrics.registry()``), so every scrape / JSON snapshot
    polls the engine exactly once. The series are emitted even when the
    engine is absent (zeros) — dashboards and BENCH records keep a stable
    schema across engine and pure-XLA runs."""
    from horovod_tpu import metrics as _metrics
    from horovod_tpu.engine import native

    reg = registry if registry is not None else _metrics.registry()
    stats = native.engine_stats() if native.available() else {}

    def bridge(name, help_, key):
        # bridged monotonic source: the raw atomic IS the running total
        reg.counter(name, help_).labels().set_total(stats.get(key, 0))

    bridge("hvt_engine_cycles_total",
           "background engine cycle-loop iterations", "cycles")
    bridge("hvt_engine_tensors_submitted_total",
           "collectives submitted to the engine", "tensors_submitted")
    bridge("hvt_engine_tensors_coordinated_total",
           "tensor names executed via coordinated responses",
           "tensors_coordinated")
    bridge("hvt_cache_hits_total",
           "response-cache hits (fast-path negotiations skipped)",
           "cache_hits")
    bridge("hvt_cache_misses_total",
           "cache-eligible lookups that missed", "cache_misses")
    bridge("hvt_fusion_buffer_bytes_total",
           "payload bytes moved through the fusion buffer",
           "fusion_bytes")
    bridge("hvt_responses_fused_total",
           "responses merged by tensor fusion (coordinator-side)",
           "responses_fused")
    bridge("hvt_engine_stalls_total",
           "stall-inspector warnings (some ranks missing a tensor)",
           "stall_events")
    bridge("hvt_ctrl_tx_bytes_total",
           "control-plane frame bytes sent by this rank (star and "
           "tree links; negotiation cost, includes frame length prefixes)",
           "ctrl_tx_bytes")
    bridge("hvt_ctrl_rx_bytes_total",
           "control-plane frame bytes received by this rank (star and "
           "tree links)",
           "ctrl_rx_bytes")
    bridge("hvt_ctrl_bypass_cycles_total",
           "cycles served by the steady-state control-plane bypass "
           "(positions-form responses rebuilt from the cache)",
           "ctrl_bypass_cycles")
    # direct control-plane peers this rank serves — a gauge: star
    # rank 0 reports world-1, tree rank 0 one per host with a leader
    # (the host count; one less when rank 0 has a host to itself)
    reg.gauge(
        "hvt_ctrl_peers",
        "direct control-plane peers this rank exchanges frames with "
        "per cycle (HVT_CTRL_TOPOLOGY)").labels().set(
            stats.get("ctrl_peers", 0))
    # flight-recorder ring overflow: events overwritten before any
    # drainer pulled them — nonzero means the timeline/analyzer view has
    # silent gaps (drain more often or record less)
    reg.counter(
        "hvt_events_dropped_total",
        "flight-recorder events overwritten in the ring before being "
        "drained (silent event loss)").labels().set_total(
            native.events_dropped())

    exec_s = reg.counter("hvt_engine_exec_seconds_total",
                         "data-plane execution time by collective op",
                         ("op",))
    exec_n = reg.counter("hvt_engine_exec_total",
                         "data-plane responses executed by collective op",
                         ("op",))
    # per-(op, codec) wire bytes off the engine's codec_tx_bytes block
    # (codec "none" = raw transfers, so summing the codec label
    # reproduces the per-op totals; replaced the old single-mode
    # hvt_wire_compression_mode gauge)
    wire_tx = reg.counter(
        "hvt_wire_tx_bytes_total",
        "bytes sent on the TCP data plane by collective op and wire "
        "codec (compressed transfers count their compressed size)",
        ("op", "codec"))
    wire_txc = reg.counter(
        "hvt_wire_tx_compressed_bytes_total",
        "TCP data-plane bytes sent in compressed form "
        "(HVT_WIRE_COMPRESSION), by collective op", ("op",))
    ns = stats.get("exec_ns", {})
    cnt = stats.get("exec_count", {})
    tx = stats.get("wire_tx_bytes", {})
    txc = stats.get("wire_tx_comp_bytes", {})
    codec_tx = stats.get("codec_tx_bytes", {})
    for op in native.STATS_OPS:
        exec_s.labels(op=op).set_total(ns.get(op, 0) / 1e9)
        exec_n.labels(op=op).set_total(cnt.get(op, 0))
        wire_txc.labels(op=op).set_total(txc.get(op, 0))
        per_codec = {codec: codec_tx.get(codec, {}).get(op, 0)
                     for codec in native.WIRE_CODECS}
        if not any(per_codec.values()) and tx.get(op, 0):
            # stale .so without the per-codec block: split its per-op
            # total by the compressed counter instead of dropping it —
            # the compressed portion belongs to the single stale-world
            # mode (wire_compression() decodes it from the old scalar),
            # only the remainder actually moved raw
            t = tx.get(op, 0)
            c = min(txc.get(op, 0), t)
            per_codec["none"] = t - c
            if c:
                _, inter, _ = native.wire_compression()
                stale_codec = (native.WIRE_CODECS[inter]
                               if 0 <= inter < len(native.WIRE_CODECS)
                               else "none")
                per_codec[stale_codec] += c
        for codec, val in per_codec.items():
            wire_tx.labels(op=op, codec=codec).set_total(val)

    # engine-side latency histograms, bridged bucket-for-bucket: the
    # C++ bounds (1 µs · 4^i) are exactly DEFAULT_LATENCY_BUCKETS, so
    # set_state maps them 1:1 (ns → seconds for the sum)
    for name, help_, key in (
            ("hvt_cycle_duration_seconds",
             "engine cycle wall time (includes the control-plane wait "
             "for peers)", "cycle_hist"),
            ("hvt_engine_wakeup_latency_seconds",
             "submit-to-drain coalescing latency of the event-driven "
             "cycle loop", "wakeup_hist")):
        h = reg.histogram(name, help_)
        d = stats.get(key) or {}
        h.labels().set_state(d.get("buckets", ()),
                             d.get("sum_ns", 0) / 1e9,
                             d.get("count", 0))

    # self-healing links (csrc/transport.h): transparent reconnects by
    # plane, plus the replay volume — a rising reconnect counter with
    # zero aborts is a flaky fabric being absorbed; pair with the
    # per-link state in hvt.diagnostics()/debugz to find WHICH link
    link_rec = reg.counter(
        "hvt_link_reconnects_total",
        "transparent link reconnects (transient socket failures healed "
        "by the transport layer without an abort), by link plane",
        ("plane",))
    lr = stats.get("link_reconnects", {})
    for plane in native.STATS_LINK_PLANES:
        link_rec.labels(plane=plane).set_total(lr.get(plane, 0))
    bridge("hvt_frames_replayed_total",
           "whole control-plane frames re-sent from the replay ring "
           "after a link reconnect",
           "frames_replayed")
    bridge("hvt_link_replay_bytes_total",
           "bytes re-sent from the per-link replay ring "
           "(HVT_REPLAY_BUDGET_BYTES) after reconnects, both planes",
           "replay_bytes")

    # per-lane execution pool (HVT_LANE_WORKERS): how many responses
    # ran on a pool worker instead of the engine thread, and the
    # configured pool size — zero tasks with a nonzero pool means the
    # traffic was pool-ineligible (global lane, shm/hierarchical
    # backend, EF/auto-codec) and still serializes on the engine thread
    bridge("hvt_lane_pool_tasks_total",
           "responses executed by the per-lane worker pool "
           "(HVT_LANE_WORKERS) instead of the engine thread",
           "lane_pool_tasks")
    reg.gauge(
        "hvt_lane_workers",
        "configured per-lane execution pool size (HVT_LANE_WORKERS; "
        "0 = single-thread engine)").set(
        stats.get("lane_workers", 0))

    # error feedback: resident residual bytes + buffers the
    # HVT_EF_MAX_BYTES budget evicted/refused (a rising drop counter
    # means quantization is running uncompensated — raise the budget)
    reg.gauge(
        "hvt_ef_residual_bytes",
        "resident error-feedback residual bytes (per-tensor fp32 "
        "quantization-error memory, bounded by HVT_EF_MAX_BYTES)").set(
            stats.get("ef_residual_bytes", 0))
    reg.counter(
        "hvt_ef_residuals_dropped_total",
        "error-feedback residual buffers evicted or refused by the "
        "HVT_EF_MAX_BYTES budget").labels().set_total(
            stats.get("ef_residuals_dropped", 0))

    # per-set lane telemetry (serving gangs): lane "0" is the global
    # set, process-set lanes hash onto "1".."7" (collisions merge
    # telemetry only — see csrc/engine.h LaneSlot)
    reg.gauge("hvt_engine_lanes_active",
              "distinct process-set lanes the engine has served since "
              "init (1 = global-only traffic)").set(
                  stats.get("lanes_active", 0))
    lane_depth = reg.gauge(
        "hvt_lane_depth",
        "pending engine collectives per lane bucket (0 = global lane; "
        "the serving autoscaler's backlog signal)", ("lane",))
    lane_s = reg.counter(
        "hvt_lane_exec_seconds_total",
        "data-plane execution time per lane bucket", ("lane",))
    lane_n = reg.counter(
        "hvt_lane_exec_total",
        "data-plane responses executed per lane bucket", ("lane",))
    # head-of-line wait per lane: submit → engine-thread pickup on
    # this rank — the in-rank service-start delay a hot neighbor
    # executing inline causes and HVT_LANE_WORKERS relieves; a lane
    # whose hol seconds climb while its exec seconds stay flat is
    # being starved by a neighbor, not slow itself
    hol_s = reg.counter(
        "hvt_lane_hol_seconds_total",
        "head-of-line wait (submit -> engine pickup) per lane bucket",
        ("lane",))
    hol_n = reg.counter(
        "hvt_lane_hol_total",
        "submissions with a measured head-of-line wait per lane "
        "bucket", ("lane",))
    depth = stats.get("lane_depth") or ()
    lane_ns = stats.get("lane_exec_ns") or ()
    lane_cnt = stats.get("lane_exec_count") or ()
    lane_hol_ns = stats.get("lane_hol_ns") or ()
    lane_hol_cnt = stats.get("lane_hol_count") or ()
    for i in range(native.STATS_LANE_SLOTS):
        lane = str(i)
        lane_depth.labels(lane=lane).set(
            depth[i] if i < len(depth) else 0)
        lane_s.labels(lane=lane).set_total(
            (lane_ns[i] if i < len(lane_ns) else 0) / 1e9)
        lane_n.labels(lane=lane).set_total(
            lane_cnt[i] if i < len(lane_cnt) else 0)
        hol_s.labels(lane=lane).set_total(
            (lane_hol_ns[i] if i < len(lane_hol_ns) else 0) / 1e9)
        hol_n.labels(lane=lane).set_total(
            lane_hol_cnt[i] if i < len(lane_hol_cnt) else 0)

    # transport backend (csrc/uring_link.h): which data-plane link
    # implementation this gang resolved HVT_LINK_BACKEND to, as an
    # info-style gauge (1 on the active backend's label), plus the
    # per-backend syscall economics — the generic pump's poll/send/recv
    # count vs the io_uring ring's SQE/enter/CQE counters. The sweep's
    # syscalls-per-op column is pump_syscalls (tcp) or uring_enters
    # (io_uring) over exec_count.
    backend_id = stats.get("link_backend", 0)
    link_backend = reg.gauge(
        "hvt_link_backend",
        "resolved data-plane link backend (HVT_LINK_BACKEND; 1 on the "
        "active backend's label)", ("backend",))
    for i, name in enumerate(native.LINK_BACKENDS):
        link_backend.labels(backend=name).set(
            1 if backend_id == i else 0)
    bridge("hvt_pump_syscalls_total",
           "syscalls (poll/send/recv) issued by the generic duplex "
           "pump fallback loop",
           "pump_syscalls")
    bridge("hvt_uring_sqes_total",
           "io_uring submission-queue entries prepared by the "
           "IoUringLink data plane",
           "uring_sqes")
    bridge("hvt_uring_enters_total",
           "io_uring_enter submit/wait syscalls issued by the "
           "IoUringLink data plane",
           "uring_enters")
    bridge("hvt_uring_cqes_total",
           "io_uring completions reaped by the IoUringLink data plane",
           "uring_cqes")

    # failure containment: coordinated aborts by cause + the sticky
    # broken flag (alerts page on either; the cause label says whether
    # it was a deadline, a dropped peer, a missed heartbeat, or a
    # forwarded ABORT frame)
    abort_c = reg.counter(
        "hvt_engine_aborts_total",
        "coordinated engine aborts by cause (sticky broken state; at "
        "most one per engine run)", ("cause",))
    ab = stats.get("aborts", {})
    for cause in native.ABORT_CAUSES:
        abort_c.labels(cause=cause).set_total(ab.get(cause, 0))
    broken, _info = native.engine_broken()
    reg.gauge("hvt_engine_broken",
              "1 while the engine is in the sticky broken state "
              "(shutdown + re-init to recover)").set(1 if broken else 0)

    up = reg.gauge("hvt_engine_up",
                   "1 when the C++ engine is initialized")
    running = native.engine_running()
    up.set(1 if running else 0)
    reg.gauge("hvt_engine_size",
              "engine world size (0 when not running)").set(
                  native.engine_size() if running else 0)

    # stall details from the diagnostics snapshot: one series per
    # stalled tensor, value = how many ranks are missing. Resolved
    # stalls zero out (the series stays, so alerts see the recovery).
    stall_g = reg.gauge(
        "hvt_stall_missing_ranks",
        "ranks that have not submitted a stalled tensor, by tensor",
        ("tensor",))
    try:
        stalls = {s["tensor"]: len(s.get("missing_ranks", []))
                  for s in (native.diagnostics() or {}).get("stalls", [])}
    except Exception:
        stalls = {}
    for labels, child in stall_g.samples():
        if labels.get("tensor") not in stalls:
            child.set(0)
    for tensor, n_missing in stalls.items():
        stall_g.labels(tensor=tensor).set(n_missing)


def start_timeline(file_path: str, mark_cycles: bool = False,
                   xla_profiler: bool = True):
    """Begin recording a Chrome-trace timeline (reference
    ``operations.cc:738``, ``basics.py:75``).

    ``xla_profiler=True`` (default) also arms an XLA/PJRT profiler
    session writing device activity to ``<file_path>.xplane/``; pass
    ``False`` for the control-plane-only trace (e.g. when you manage
    your own ``jax.profiler`` session or want zero device overhead)."""
    _ensure_init()
    from horovod_tpu.utils import timeline as _tl

    _tl.start(file_path, mark_cycles=mark_cycles, xla_profiler=xla_profiler)


def stop_timeline():
    _ensure_init()
    from horovod_tpu.utils import timeline as _tl

    _tl.stop()


def startup_report(until=None) -> dict:
    """What this process's start was made of: the phases of the import
    and of ``hvt.init()``, the seconds JAX spent tracing, lowering,
    compiling and reading its cache (nested spans counted once), the
    cache's hits and misses, and the ten functions that took most to
    trace and lower with how often each was traced, lowered and compiled
    (``metrics/startup.py:report``; docs/troubleshooting.md, "a slow
    start"). ``until``: seconds since the epoch, to leave out what ended
    later. Works before ``init()`` and after ``shutdown()``."""
    from horovod_tpu.metrics import startup as _startup

    return _startup.report(until)


def diagnostics() -> dict:
    """Stall-diagnostics snapshot (the machine-readable face of the
    reference's stall inspector, ``stall_inspector.h`` lineage).

    Returns a JSON-serializable dict:

    - ``engine``: running flag, rank/size, cycle count, client queue
      depth, stall warn threshold, flight-recorder drop count;
    - ``pending``: tensors submitted on THIS rank still awaiting
      execution, with ages in seconds;
    - ``negotiations`` (rank 0 only): the coordinator's arrival table —
      per tensor, which ranks have announced it and which are missing,
      plus how long it has been waiting;
    - ``stalls``: the subset of negotiations past the warn threshold —
      a deliberately stalled gang names the tensor and its missing
      ranks here;
    - ``timeline_active`` / ``process_rank``: local context.

    Served remotely as ``GET /debugz`` on the rendezvous server, which
    aggregates every worker's pushed snapshot."""
    from horovod_tpu.engine import native
    from horovod_tpu.utils import timeline as _tl

    out = {"process_rank": int(os.environ.get("HVT_PROCESS_ID", "0")),
           "timeline_active": _tl.active()}
    try:
        out.update(native.diagnostics() or
                   {"engine": {"running": False}})
    except Exception as e:
        out["engine"] = {"running": False, "error": str(e)}
    return out


def _telemetry_snapshot(rank: int):
    """This worker's push payload: diagnostics + the compact telemetry
    record + the mergeable counters frame (metrics/telemetry.py)."""
    from horovod_tpu.engine import native
    from horovod_tpu.metrics import telemetry as _telemetry

    stats = native.engine_stats() if native.available() else {}
    return _telemetry.build_snapshot(rank, _telemetry.host_name(),
                                     diagnostics(), stats)


def _debugz_push_loop(addr: str, rank: int, stop: "threading.Event",
                      period_sec: float = None):
    """Push this worker's telemetry until stopped — the worker-side
    half of ``GET /debugz`` / ``GET /statusz``. Best-effort: a dead
    rendezvous server must never disturb training.

    The period is ``HVT_DEBUGZ_INTERVAL_MS`` (default 5000) with ±25%
    jitter per tick — without the jitter every rank pushes on the same
    phase, a thundering herd on the rendezvous server at 64+ ranks.
    Under ``HVT_CTRL_TOPOLOGY=tree`` (or ``HVT_TELEMETRY_AGG=1``)
    members push to their host leader, which PUTs one merged frame per
    host (``/kv/telemetry/host/<host>``) so the driver's ingest cost is
    O(hosts); star topology keeps the direct per-rank
    ``/kv/debugz/<rank>`` pushes."""
    from horovod_tpu.metrics import telemetry as _telemetry

    _telemetry.TelemetryPusher(
        addr, rank, lambda: _telemetry_snapshot(rank), stop,
        period_sec=period_sec).run()
