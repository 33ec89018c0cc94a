"""Process start to window start (s): TPU start, weights, compiles or cache
loads, warm traffic."""


def read(run):
    return run.setup["setup_s"]
