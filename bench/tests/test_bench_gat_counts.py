"""GAT's work counts (``bench/models/gat.py``) against hand counts at a
tiny shape: one partition of 10 nodes and 20 arcs, features 5, two layers
of 2 heads (3 wide, concatenated to 6; then 4 wide, averaged), 3
classes."""
import tinycell  # noqa: F401  (puts the checkout on sys.path)
from bench.models import gat

CONFIG = {"feature_dim": 5, "heads": 2, "head_dim": 3, "embed_dim": 4,
          "num_layers": 2}


def test_layer_widths():
    assert gat.layer_widths(CONFIG, 5) == [(5, 3), (6, 4)]


def test_model_flops_per_epoch():
    # layer 0 (5 -> 2x3): dense 2*10*5*6 = 600, scores 2*2*10*6 = 240,
    #   arc sum 2*20*6 = 240; backward dW 600, scores 480, arc sum 480
    # layer 1 (6 -> 2x4): dense 960, scores 320, arc sum 320; backward
    #   960 + 640 + 640, and dh 960
    # head: forward, dW and d(embedding), 2*10*4*3 each
    assert gat.flops_per_epoch(CONFIG, [10], [20], 3) == (
        2640 + 4800 + 720)
    assert gat.flops_per_epoch(CONFIG, [10, 10], [20, 20], 3) == 2 * 8160


def test_aggregation_least_work():
    # layer 0: 2*E*H*D = 240 FLOPs; rows in and out 2*10*6*4 = 480 B,
    # arcs 20*(8 + 4*2) = 320 B. layer 1: 320 FLOPs, 640 + 320 B.
    fwd = gat.aggregation_least_work(CONFIG, [10], [20], backward=False)
    assert fwd == [(240, 800), (320, 960)]
    both = gat.aggregation_least_work(CONFIG, [10], [20], backward=True)
    assert both == [(240, 800)] * 3 + [(320, 960)] * 3
