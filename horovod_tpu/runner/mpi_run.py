"""MPI launch path (reference ``horovod/runner/mpi_run.py``: impl
detection ``_get_mpi_implementation:73``, flag sets ``:32-44``, mpirun
command template ``:177-196`` incl. ``-x`` env forwarding).

``hvtrun --use-mpi`` builds ONE ``mpirun`` command that places all ranks;
each rank then reads ``OMPI_COMM_WORLD_RANK``-style env to derive its
HVT_* slot env (see ``env_from_mpi``)."""

from __future__ import annotations

import os
import shlex
import subprocess
import sys
from typing import List, Optional

from horovod_tpu.runner.hosts import pin_engine_gang_to_cpu

OPENMPI = "OpenMPI"
SPECTRUM = "Spectrum MPI"
MPICH = "MPICH"
INTEL = "IMPI"
UNKNOWN = "Unknown"

# flags matching the reference's per-implementation sets (mpi_run.py:32-44)
_BASIC_ARGS = {
    OPENMPI: ["--allow-run-as-root", "--tag-output"],
    SPECTRUM: ["--tag-output"],
    MPICH: [],
    INTEL: [],
    UNKNOWN: [],
}
# large-cluster tuning (reference adds these past 64 hosts)
_LARGE_CLUSTER_ARGS = {
    OPENMPI: ["-mca", "plm_rsh_no_tree_spawn", "true"],
    SPECTRUM: [],
    MPICH: [],
    INTEL: [],
    UNKNOWN: [],
}
_LARGE_CLUSTER_THRESHOLD = 64


def get_mpi_implementation(mpirun: str = "mpirun") -> Optional[str]:
    """Probe ``mpirun --version`` (reference
    _get_mpi_implementation:73). None when mpirun is absent."""
    try:
        out = subprocess.run([mpirun, "--version"], capture_output=True,
                             text=True, timeout=10)
    except (FileNotFoundError, subprocess.TimeoutExpired):
        return None
    text = out.stdout + out.stderr
    if "Open MPI" in text or "OpenRTE" in text:
        return OPENMPI
    if "IBM Spectrum MPI" in text:
        return SPECTRUM
    if "MPICH" in text or "HYDRA" in text:
        return MPICH
    if "Intel(R) MPI" in text:
        return INTEL
    return UNKNOWN


def env_forward_args(impl: str, env_keys: List[str]) -> List[str]:
    """Per-implementation env forwarding (-x for OpenMPI family,
    -genvlist for MPICH/Intel)."""
    if impl in (OPENMPI, SPECTRUM, UNKNOWN):
        out = []
        for k in env_keys:
            out += ["-x", k]
        return out
    return ["-genvlist", ",".join(env_keys)] if env_keys else []


def build_mpirun_command(np: int, hosts: str, command: List[str],
                         env: dict, impl: str = OPENMPI,
                         ssh_port: Optional[int] = None,
                         extra_args: Optional[List[str]] = None
                         ) -> List[str]:
    """Assemble the single mpirun invocation (reference
    mpi_run.py:177-196)."""
    host_list = [h for h in hosts.split(",") if h]
    cmd = ["mpirun", "-np", str(np)]
    cmd += _BASIC_ARGS.get(impl, [])
    if len(host_list) > _LARGE_CLUSTER_THRESHOLD:
        cmd += _LARGE_CLUSTER_ARGS.get(impl, [])
    if impl in (OPENMPI, SPECTRUM, UNKNOWN):
        cmd += ["-H", hosts]
        if ssh_port:
            cmd += ["-mca", "plm_rsh_args", f"-p {ssh_port}"]
    else:
        cmd += ["-hosts", ",".join(h.split(":")[0] for h in host_list)]
    forward = sorted(k for k in env
                     if k.startswith("HVT_")
                     or k in ("PATH", "PYTHONPATH", "JAX_PLATFORMS"))
    cmd += env_forward_args(impl, forward)
    cmd += extra_args or []
    cmd += command
    return cmd


def env_from_mpi(base_env: Optional[dict] = None) -> dict:
    """Derive HVT_* slot env from the MPI launcher's environment, so a
    process started by mpirun (not hvtrun) self-configures — the analog
    of the reference reading OMPI env in MPI mode."""
    env = dict(os.environ if base_env is None else base_env)
    pairs = [
        ("HVT_PROCESS_ID", ["OMPI_COMM_WORLD_RANK", "PMI_RANK"]),
        ("HVT_NUM_PROCESSES", ["OMPI_COMM_WORLD_SIZE", "PMI_SIZE"]),
        ("HVT_LOCAL_PROCESS_ID", ["OMPI_COMM_WORLD_LOCAL_RANK",
                                  "MPI_LOCALRANKID"]),
        ("HVT_LOCAL_SIZE", ["OMPI_COMM_WORLD_LOCAL_SIZE",
                            "MPI_LOCALNRANKS"]),
    ]
    out = {}
    for hvt_key, mpi_keys in pairs:
        if env.get(hvt_key):
            continue
        for mk in mpi_keys:
            if env.get(mk):
                out[hvt_key] = env[mk]
                break
    return out


def mpi_run(args, slots, master_addr: str) -> int:
    """Execute the job through mpirun (called from hvtrun with
    --use-mpi). All ranks share one command; slot identity comes from the
    MPI env at worker startup."""
    impl = get_mpi_implementation()
    if impl is None:
        print("[hvtrun] mpirun not found on PATH", file=sys.stderr)
        return 1
    env = dict(os.environ)
    env.update({
        "HVT_CYCLE_TIME_MS": str(args.cycle_time_ms),
        "HVT_FUSION_THRESHOLD": str(args.fusion_threshold_mb << 20),
        "HVT_FROM_MPI": "1",
    })
    # mirror slot_env's backend split (launch.py): engine → C++ control
    # star; jax → jax.distributed coordinator
    if getattr(args, "backend", "engine") == "jax":
        env["HVT_COORDINATOR_ADDR"] = f"{master_addr}:{args.master_port}"
    else:
        env["HVT_MASTER_ADDR"] = master_addr
        env["HVT_MASTER_PORT"] = str(args.master_port)
        pin_engine_gang_to_cpu(env, slots)
    hosts = ",".join(sorted({f"{s.hostname}:{s.local_size}"
                             for s in slots}))
    cmd = build_mpirun_command(args.num_proc, hosts, list(args.command),
                               env, impl=impl, ssh_port=args.ssh_port)
    if args.verbose:
        print("[hvtrun] " + " ".join(shlex.quote(c) for c in cmd),
              file=sys.stderr)
    return subprocess.run(cmd, env=env).returncode
