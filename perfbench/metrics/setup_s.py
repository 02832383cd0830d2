"""Process start -> window edge a, less the comparison's own copying."""


def read(run):
    return run["setup_s"]
