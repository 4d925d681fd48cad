"""Process start to the window's start, s: loading, compiling or
fetching every program, and the warm-up traffic."""


def read(run):
    return run.setup_s
