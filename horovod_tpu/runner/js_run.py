"""IBM LSF ``jsrun`` launch path (reference ``horovod/runner/js_run.py``
+ ``runner/util/lsf.py``): on LSF clusters the host list comes from
``LSB_MCPU_HOSTS``/``LSB_HOSTS`` and placement is delegated to jsrun
resource sets."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from typing import Dict, List, Optional

from horovod_tpu.runner.hosts import pin_engine_gang_to_cpu


def in_lsf_env(env: Optional[dict] = None) -> bool:
    env = os.environ if env is None else env
    return "LSB_JOBID" in env


def lsf_hosts(env: Optional[dict] = None) -> Dict[str, int]:
    """Parse LSF's host allocation. ``LSB_MCPU_HOSTS`` is
    ``host1 n1 host2 n2 ...``; fall back to counting ``LSB_HOSTS``
    entries. Batch/launch nodes are excluded like the reference."""
    env = os.environ if env is None else env
    hosts: Dict[str, int] = {}
    mcpu = env.get("LSB_MCPU_HOSTS", "")
    first_host = None
    if mcpu:
        toks = mcpu.split()
        for i in range(0, len(toks) - 1, 2):
            if first_host is None:
                first_host = toks[i]
            hosts[toks[i]] = int(toks[i + 1])
    else:
        for h in env.get("LSB_HOSTS", "").split():
            if first_host is None:
                first_host = h
            hosts[h] = hosts.get(h, 0) + 1
    # LSF lists the batch (launcher) host first; drop it by POSITION, not
    # by name — compute nodes may legitimately be named batch*
    if first_host is not None and len(hosts) > 1:
        hosts.pop(first_host, None)
    return hosts


def build_jsrun_command(np: int, command: List[str],
                        smpiargs: str = "-disable_gpu_hooks"
                        ) -> List[str]:
    """One resource set per rank (reference js_run.py builds
    ``jsrun -n<np> -a1 -cALL_CPUS -g<gpus>``; TPU hosts expose no GPUs so
    the resource set is CPU-only)."""
    cmd = ["jsrun", f"-n{np}", "-a1", "-cALL_CPUS"]
    if smpiargs:
        cmd += ["--smpiargs", smpiargs]
    cmd += command
    return cmd


def js_run(args, slots, master_addr: str) -> int:
    # placement is jsrun's job; identity comes from MPI env
    if shutil.which("jsrun") is None:
        print("[hvtrun] jsrun not found on PATH", file=sys.stderr)
        return 1
    env = dict(os.environ)
    env.update({
        "HVT_CYCLE_TIME_MS": str(args.cycle_time_ms),
        "HVT_FUSION_THRESHOLD": str(args.fusion_threshold_mb << 20),
        "HVT_FROM_MPI": "1",   # jsrun provides MPI-style rank env
    })
    if getattr(args, "backend", "engine") == "jax":
        env["HVT_COORDINATOR_ADDR"] = f"{master_addr}:{args.master_port}"
    else:
        env["HVT_MASTER_ADDR"] = master_addr
        env["HVT_MASTER_PORT"] = str(args.master_port)
        pin_engine_gang_to_cpu(env, slots)
    cmd = build_jsrun_command(args.num_proc, list(args.command))
    return subprocess.run(cmd, env=env).returncode
