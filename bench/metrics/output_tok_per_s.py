"""Output tokens emitted in the window over the window's seconds."""
from readings import window_tokens


def read(run):
    return window_tokens(run) / run.window_s
