"""Images trained per second by the whole job over the window: steps
between the two device-fetched edges x global batch / host-clock time."""


def read(run):
    return run["images"] / run["window_s"]
