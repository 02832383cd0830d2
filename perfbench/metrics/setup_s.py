"""Process start -> the stamp of the job's last warm-up step (its 8th),
less the comparison's own copying.  The steps the benchmark then runs
until no stock of packed batches is left are not set-up."""


def read(run):
    return run["setup_s"]
