"""Device (train): the architecture's own count of operations a token."""

from benchmark import models


def read(run):
    counts = models.adapter(run["config"]["arch"]).counts
    return counts.train_flops_per_token(run["config"], run["seq"])
