"""A run's set-up by phase, from the package's own recorder
(``horovod_tpu/metrics/startup.py``, PR 35): what the six readers
``layer_metrics/{init_s,devices_s,trace_s,lower_s,cache_read_s,
setup_unnamed_s}.py`` share.

Set-up only: spans that end within ``run["setup_seconds"]`` of the
recorder's first timestamp. The package is imported 2 to 3 s after the
harness starts its clock, so the cut falls 2 to 3 s after warm-up ends,
among the traced steps and the window, where a compile fails the run and
so no span is.

    import_s + init_s + trace_s + lower_s + backend_compile_s
        + cache_read_s + setup_unnamed_s = setup_seconds

``devices_s`` is inside ``init_s``. The stages' seconds are exclusive: a
second that two of JAX's spans cover belongs to the inner one, and a
cache read is inside a ``backend_compile`` span, so the harness's
``compile_s`` is about ``backend_compile_s + cache_read_s``.
"""


def split(run):
    """The phases' seconds by name, or None where the program has no
    recorder (a program from before PR 35) or the recorder holds
    nothing."""
    try:
        from horovod_tpu.metrics import startup
    except ImportError:
        return None
    first = startup.recorder().first_timestamp()
    if first is None:
        return None
    report = startup.report(until=first + run["setup_seconds"])
    phases = {}
    for p in report["phases"]:
        phases[p["phase"]] = phases.get(p["phase"], 0.0) + p["seconds"]
    out = {f"{name}_s": phases.get(name) for name in ("import", "init",
                                                       "devices")}
    for stage, total in report["stages"].items():
        out[f"{stage}_s"] = total["seconds"]
    named = ((out["import_s"] or 0.0) + (out["init_s"] or 0.0)
             + sum(t["seconds"] for t in report["stages"].values()))
    out["setup_unnamed_s"] = run["setup_seconds"] - named
    return out


def read(name, run):
    found = split(run)
    return None if found is None else found[name]
