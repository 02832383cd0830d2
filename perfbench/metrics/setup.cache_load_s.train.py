"""Series `cache_load` at edge b: seconds spent fetching compiled programs
from the persistent cache (lookup, deserialize, load) since the job
started."""

from perfbench.harness.series import at_b


def read(run):
    v = at_b(run, "cache_load", witness="init_params")
    return None if v is None else v[0]
