"""MiB of host arrays the driver puts on the device per step, counted from
the shapes of the batch it builds."""


def read(run):
    return run.h2d_bytes / 2**20 if run.h2d_bytes else None
