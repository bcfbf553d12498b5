"""Process start to the first timed request: weights, compression, load,
and the closed-loop ramp that compiles the cell's shapes."""


def read(run):
    return run.setup["setup_s"]
