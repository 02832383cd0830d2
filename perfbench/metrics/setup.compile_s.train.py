"""Series `compile` at edge b: seconds the backend spent compiling
programs since the job started (nothing compiles inside the window, so
this is set-up's)."""

from perfbench.harness.series import at_b


def read(run):
    v = at_b(run, "compile", witness="init_params")
    return None if v is None else v[0]
