"""XLA backend compiles the program itself counted in the window: the
gain of its ``engine.compiles`` counter (a listener on JAX's
``backend_compile_duration`` event, process-wide, so retraces count)
between the registry snapshots at the window's start and end.  Set-up
should leave none.  None where the program has no such counter."""


def _total(reg):
    m = reg.get("metrics", {}).get("engine.compiles")
    return None if m is None else sum(v["value"] for v in m["values"])


def read(run):
    c0, c1 = _total(run.reg0), _total(run.reg1)
    return None if c1 is None else c1 - (c0 or 0.0)
