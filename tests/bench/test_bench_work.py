"""``bench/work.py`` counts the paper's operations, and ``bench/weights.py``
builds the tree the program's graph expects."""
import json
from pathlib import Path

import pytest

from bench import arch, weights, work
from repro.core.cycle_model import NETWORKS
from repro.models.engine import conv_layers_for_graph
from repro.models.graph import CnnConfig, build_graph, graph_spec

ROOT = Path(__file__).resolve().parents[2]
NETS = ["resnet18", "vgg16"]


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", NETS)
def test_conv_ops_are_twice_the_papers_macs(name):
    cfg = config(name)
    main = [l for l in work.conv_work(cfg) if not l.name.endswith(".ds")]
    assert sum(l.ops for l in main) == 2 * sum(l.macs for l in NETWORKS[name])


@pytest.mark.parametrize("name", NETS)
def test_model_ops_are_every_weighted_layer_plus_the_head(name):
    cfg = config(name)
    cnn = CnnConfig(name, 1.0, 1000)
    dims = conv_layers_for_graph(cnn, build_graph(cnn))
    assert work.model_ops(cfg) == 2 * sum(d.macs for d in dims.values()) + 2 * 512 * 1000


@pytest.mark.parametrize("name", NETS)
def test_weight_layout_is_the_programs_parameter_tree(name):
    spec = graph_spec(CnnConfig(name, 1.0, 1000))
    layout = {n: (w, b) for n, w, b in weights.layout(config(name))}
    assert set(layout) == set(spec)
    for n, leaves in spec.items():
        w = leaves["w"] if "w" in leaves else leaves["kernel"]
        b = leaves["b"] if "b" in leaves else leaves["bias"]
        assert layout[n] == (tuple(w.shape), tuple(b.shape)), n


def test_conv_min_seconds_takes_the_larger_bound_per_layer():
    cfg = config("resnet18")
    peaks = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e12}
    want = sum(
        max(3 * l.ops, 3 * l.act_bytes + 2 * l.weight_bytes) / 1e12 for l in work.conv_work(cfg)
    )
    assert work.conv_min_seconds(cfg, 3, 2, peaks) == pytest.approx(want)


def test_resnet_shortcuts_sit_at_the_stage_transitions():
    names = [c.name for c in arch.convs(config("resnet18"))]
    assert [n for n in names if n.endswith(".ds")] == ["C6.ds", "C10.ds", "C14.ds"]
    assert len(names) == 20


def test_seeds_beyond_32_bits_give_other_weights():
    assert list(weights.key_data(5)) != list(weights.key_data(2**33 + 5))
    assert list(weights.key_data(7)) == list(weights.key_data(7))
