"""Tokens of every step of the window over the window and the chips. The
window opens after warm-up and closes when the step in flight at `--seconds`
ends (each step ended by a device_get of its loss), so whole steps are
counted over exactly the time they took. host_clock."""

from benchmark.stats import train_rate


def read(run):
    return train_rate(run)
