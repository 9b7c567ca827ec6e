"""The benchmark's operation and byte counts against hand-worked shapes."""
import counts


def test_segment_spmm_counts_by_hand():
    # 2 segments of 10 valid nodes and 30 valid edges at width 4:
    # FLOPs 2 x (30 edges x 4 lanes x (1 multiply + 1 add)) = 480;
    # bytes 2 x 4 B x (read 10x4 + write 10x4 + src, dst, w of 30) = 1,360
    c = counts.segment_spmm(2, 10, 30, 4)
    assert c == {"flops": 480.0, "bytes": 1360.0}


def test_sed_pool_counts_by_hand():
    # 3 graphs of 5 segments at width 8: 3 x 2 x 5 x 8 = 240 FLOPs;
    # 3 x 4 B x (5x8 embeddings + 3x5 masks + 8 out) = 756 bytes
    c = counts.sed_pool(3, 5, 8)
    assert c == {"flops": 240.0, "bytes": 756.0}


def test_train_flops_per_graph_by_hand():
    cfg = {"hidden": 2, "n_feat": 3, "n_pre": 1, "n_mp": 1, "n_post": 1,
           "head": "segment_sum"}
    # one segment of 4 nodes and 6 edges:
    #   pre  2*4*3*2 = 48, forward + weight gradient      -> 96
    #   mp   4*4*2*2 = 64, post 2*4*2*2 = 32: 96 x 3      -> 288
    #   neighbour sum 2*6*2 = 24, forward + transpose     -> 48
    #   mean pool 4*2 = 8, forward + backward             -> 16
    # head 2 x (2*7*2) + 2*1*2 = 60; pooling 2*7 + 2*1 = 16
    assert counts.train_flops_per_graph(cfg, 1, 4, 6, 7) == 96 + 288 + 48 \
        + 16 + 60 + 16


def test_train_flops_per_graph_mlp_head_by_hand():
    cfg = {"hidden": 2, "n_feat": 3, "n_pre": 1, "n_mp": 1, "n_post": 1,
           "head": "mlp", "n_out": 5}
    # the encoder as above: 96 + 288 + 48 + 16 = 448 per sampled segment;
    # head 3 x (2*2*2 + 2*2*5) = 84 on the pooled embedding;
    # pooling 2*7*2 over the graph's segments + 2*1*2 back to the sampled
    assert counts.train_flops_per_graph(cfg, 1, 4, 6, 7) == 448 + 84 + 28 \
        + 4


def test_mfu_reads_the_untraced_rate():
    """``mfu.train`` multiplies the useful FLOPs per graph, as the
    configuration's reference counts them, by the rate of the untraced
    window, which the run hands it as ``graphs_per_s``, over the chips."""
    from types import SimpleNamespace

    from conftest import BENCH
    from harness import spec as SPEC
    cfg = {"hidden": 2, "n_feat": 3, "n_pre": 1, "n_mp": 1, "n_post": 1,
           "head": "segment_sum"}
    run = SimpleNamespace(
        cfg=cfg, traffic={"num_sampled": 1}, chips=1, graphs_per_s=1e6,
        stats={"nodes": 4, "edges": 6, "segments": 7},
        peak={"bf16_flops_per_s": 1e12},
        reference=SPEC.module(BENCH, "references", "gnn"))
    # 524 FLOPs per graph (above) x 1e6 graphs/s over 1e12 FLOP/s
    assert SPEC.reader(BENCH, "mfu.train")(run) == 100.0 * 524e6 / 1e12
    run.chips = 4
    assert SPEC.reader(BENCH, "mfu.train")(run) == 100.0 * 524e6 / 4e12
