"""Device (collectives): share of the traced window in which a collective
(all-gather, reduce-scatter, all-reduce, all-to-all, collective-permute) was
in flight on a chip and no other instruction ran there, averaged over the
chips. device_trace."""


def read(run):
    data = run["trace_data"]
    if data is None:
        return None
    return 100.0 * data.collective_exposed_s() / data.window_s
