"""Process start to window start: compile or cache reads, weights, engine,
warm-up and the traffic's own set-up."""


def read(run):
    return run.setup_s
