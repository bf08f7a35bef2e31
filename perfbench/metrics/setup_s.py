"""Process start to the first timed simulation: JAX and chip start-up,
cache load or compile, and the set-up simulation (host clock)."""


def read(run):
    return run.setup_s
