"""Process start to the first instant of the measured window: cluster, TPU
runtime, weights, every compile or cache read, warm-up, the correctness
check. host_clock."""


def read(run):
    return run["setup_s"]
