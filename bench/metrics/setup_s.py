"""Process start to the first request of the window: loading, weights,
warm-up (with compilation in a cold run) and the traffic's ramp."""


def read(run):
    return run.setup_s
