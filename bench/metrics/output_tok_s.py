"""Generated tokens delivered inside the window, over its seconds."""

from bench import window


def read(run):
    return window.delivered_tokens(run.rec) / run.rec.seconds
