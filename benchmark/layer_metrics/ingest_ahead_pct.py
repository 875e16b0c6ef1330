"""Train scheduler (data/iterator.py): the share of the traced window's
`data.iter.take` spans (the consumer's take of a batch from the iterator's
queue) whose `ready` is 1: the batch was made, and for a JAX batch placed on
the device, before it was asked for. A program that makes its batches inline
opens no such span and leaves the metric out. program_span."""

from benchmark import program_trace


def read(run):
    t = program_trace.load(run)
    takes = t.named("data.iter.take") if t else []
    if not takes:
        return None
    return 100.0 * sum(s.args.get("ready", 0) for s in takes) / len(takes)
