"""Valid node slots over padded node slots, over the valid segments of the
cell's dataset, in percent."""


def read(run):
    return 100.0 * run.stats["seg_fill"]
