"""The whole step's share of the chips' bf16 peak: useful forward and
backward FLOPs per graph (the configuration's reference counts them,
``train_flops_per_graph``) times the graphs per second of the run's
untraced window, over chips times peak, in percent."""


def read(run):
    per_graph = run.reference.train_flops_per_graph(run.cfg, run.traffic,
                                                    run.stats)
    return 100.0 * per_graph * run.graphs_per_s / (
        run.chips * run.peak["bf16_flops_per_s"])
