"""Series `init_params` at edge b: seconds `CaffeProcessor._init_params`
took (parameters and optimizer state, leaf by leaf)."""

from perfbench.harness.series import at_b


def read(run):
    v = at_b(run, "init_params")
    return None if v is None else v[0]
