"""JAX executables made inside the window (compiles and persistent-cache
loads, from JAX's ``backend_compile`` monitoring event): set-up should
leave none."""


def read(run):
    return run.compiles.between(run.t0, run.t1)
