"""Work counted from shapes (bench/counts.py and the GCN's model module,
bench/models/gcn.py) against hand counts at a tiny shape: one partition of
10 nodes and 20 arcs, layers 4->6->6->5, 3 classes."""
import pytest

import tinycell  # noqa: F401  (puts the checkout on sys.path)
from bench import counts
from bench.models import gcn

CONFIG = {"feature_dim": 4, "hidden_dim": 6, "embed_dim": 5, "num_layers": 3}
LAYERS = [(4, 6), (6, 6), (6, 5)]


def test_layer_widths():
    assert gcn.layer_widths(CONFIG, 4) == LAYERS


def test_model_flops_per_epoch():
    # layer 0: forward 160 + 480, dW 480 (no input gradient)
    # layer 1: forward 240 + 720, dW 720, da 720, dh 240
    # layer 2: forward 240 + 600, dW 600, da 600, dh 240
    # head: forward, dW and d(embedding), 2*10*5*3 each
    assert gcn.flops_per_epoch(CONFIG, [10], [20], 3) == (
        1120 + 2640 + 2280 + 900)
    # partitions add up
    assert gcn.flops_per_epoch(CONFIG, [10, 10], [20, 20], 3) == 2 * 6940


def test_aggregation_least_work():
    fwd = gcn.aggregation_least_work(CONFIG, [10], [20], backward=False)
    assert fwd == [(640, 640), (960, 720), (840, 680)]
    both = gcn.aggregation_least_work(CONFIG, [10], [20], backward=True)
    assert both == [(640, 640), (960, 720), (240, 720), (840, 680),
                    (240, 720)]


def test_least_seconds_takes_the_larger_bound():
    assert counts.least_seconds([(100, 1000)], 100, 10) == pytest.approx(100)
    assert counts.least_seconds([(100, 1000), (1000, 1)], 100, 10) == (
        pytest.approx(110))


HLO = """
  %ag = f32[4,8]{1,0} all-gather(f32[1,8]{1,0} %x), dimensions={0}
  %ars = (f32[16]{0}, bf16[8]{0}) all-reduce-start(f32[16]{0} %y, bf16[8]{0} %z)
  %ard = (f32[16]{0}, bf16[8]{0}) all-reduce-done((f32[16]{0}, bf16[8]{0}) %ars)
  %add = f32[16]{0} add(f32[16]{0} %a, f32[16]{0} %b)
"""


def test_collective_bytes():
    got = counts.collective_bytes(HLO)
    assert got == {"all-gather": 128, "all-reduce": 80, "total": 208}


def test_collective_bytes_agrees_with_the_program():
    from repro.launch.hlo_analysis import collective_bytes
    assert collective_bytes(HLO)["total"] == counts.collective_bytes(
        HLO)["total"]
