"""Host milliseconds per step in the program's ``feeder.assemble`` spans
(``graphs/batching.py::batch_iterator``: the gather of the batch's padded
segments from the host dataset), over the traced window's steps."""
import program_spans as PS


def read(run):
    return PS.per_step_ms(run, PS.span_s(run.trace, "feeder.assemble"))
