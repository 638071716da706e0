"""Process start to window start: imports, weights, engine build, warm-up
(compiles, when the cache is cold) and lead-in."""


def read(run):
    return run.setup_s
