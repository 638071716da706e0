"""Requests finished inside the window, per second (a test metric)."""


def read(run):
    done = [t for t in run.rec.finished.values() if run.rec.inside(t)]
    return len(done) / run.rec.seconds
