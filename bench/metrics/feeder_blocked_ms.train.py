"""Host milliseconds per batch that the training loop waited for the
program's feeder to deliver a batch on the devices: the registry counter
``feeder.host_blocked_ms`` over ``feeder.batches``
(``dist/pipeline.py::FeederStats``), counted over the traced window."""


def read(run):
    batches = run.counters.get("feeder.batches")
    blocked = run.counters.get("feeder.host_blocked_ms")
    if not batches or blocked is None:
        return None
    return blocked / batches
