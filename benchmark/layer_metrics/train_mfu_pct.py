"""Device (train): operations the forward and backward passes require per
token (the architecture's counts; recompute not counted) times this run's tokens a
second a chip, over the chip's peak. host_clock over a count from shapes."""

from benchmark import models, peaks
from benchmark.stats import train_rate


def read(run):
    rate = train_rate(run)
    if rate is None:
        return None
    counts = models.adapter(run["config"]["arch"]).counts
    per_token = counts.train_flops_per_token(run["config"], run["seq"])
    return 100.0 * rate * per_token / peaks.peak(
        run["device"]["kind"], "bf16_flops_per_s")
