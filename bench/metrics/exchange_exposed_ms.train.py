"""Device milliseconds per step of collective operations (the table's ring
exchange, the gradients' all-reduce) during which that device ran no other
operation, averaged over the chips, over the traced window's steps."""
import trace_reduce as TR


def read(run):
    if not run.steps or not TR.has_collectives(run.trace):
        return None
    return TR.exposed_collective_s(run.trace) / run.steps * 1e3
