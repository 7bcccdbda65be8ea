"""Programmatic launch API (reference ``horovod/runner/__init__.py:91``
``run()`` — the "interactive run" used by notebooks and the Spark/Ray
layers): run ``fn`` in ``np`` coordinated processes and return the
per-rank results ordered by rank."""

from __future__ import annotations

import os
import sys
import tempfile
from typing import Any, List, Optional


def run(fn, args=(), kwargs=None, np: int = 1,
        hosts: Optional[str] = None, env: Optional[dict] = None,
        master_port: int = 29540, force_cpu: bool = True,
        run_dir: Optional[str] = None,
        verbose: bool = False) -> List[Any]:
    """Launch ``fn(*args, **kwargs)`` across ``np`` processes through the
    hvtrun machinery; inside ``fn`` the full horovod_tpu API (rank/size,
    collectives, DistributedOptimizer) is live.

    ``force_cpu`` pins workers to the CPU JAX platform (``JAX_PLATFORMS=
    cpu``); without it the launcher still does so wherever several
    workers share a host, because a chip belongs to one process.

    Remote ``hosts`` require a filesystem shared between launcher and
    workers: pass ``run_dir`` pointing into it (the pickled function and
    per-rank results travel through that directory).
    """
    import cloudpickle

    from horovod_tpu.runner import launch as launch_mod
    from horovod_tpu.runner.codec import dumps_base64
    from horovod_tpu.runner.hosts import parse_hosts
    from horovod_tpu.runner.launch import _is_local

    if hosts and run_dir is None:
        remote = [h.hostname for h in parse_hosts(hosts)
                  if not _is_local(h.hostname)]
        if remote:
            raise ValueError(
                f"run(hosts=...) with remote hosts {remote} needs "
                f"run_dir= on a filesystem shared with those hosts — "
                f"the function and results are exchanged through it")

    kwargs = kwargs or {}
    with tempfile.TemporaryDirectory(prefix="hvt_run_",
                                     dir=run_dir) as tmp:
        fn_path = os.path.join(tmp, "fn.b64")
        with open(fn_path, "w") as f:
            f.write(dumps_base64((fn, args, kwargs)))
        argv = ["-np", str(np), "--master-port", str(master_port)]
        if hosts:
            argv += ["-H", hosts]
        if verbose:
            argv += ["--verbose"]
        argv += [sys.executable, "-m", "horovod_tpu.runner.task_runner",
                 fn_path, tmp]
        extra = dict(env or {})
        if force_cpu:
            extra["JAX_PLATFORMS"] = "cpu"
        old = {k: os.environ.get(k) for k in extra}
        os.environ.update(extra)
        try:
            rc = launch_mod.main(argv)
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        if rc != 0:
            raise RuntimeError(f"hvt.runner.run failed with exit code {rc}")
        results = []
        for rank in range(np):
            path = os.path.join(tmp, f"result_{rank}.pkl")
            if not os.path.exists(path):
                raise RuntimeError(f"rank {rank} produced no result")
            with open(path, "rb") as f:
                results.append(cloudpickle.load(f))
        return results
