"""A configuration, a traffic mix and a metric are added as files and
entries, and found by name, with no edit to a file that is there."""
import json
import os
import shutil

from bench import common
from bench.tests.cases import ROOT


def _checkout_with_an_added_cell(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench")
    bm = common.load_benchmark(ROOT)
    (root / "bench" / "configs" / "ring.json").write_text(json.dumps(
        {"name": "ring", "generator": "ring", "scale": 1.0, "num_nodes": 1000, "num_edges": 1000}))
    (root / "bench" / "generators" / "ring.py").write_text(
        "import numpy as np\n\n"
        "def edges(cfg, seed):\n"
        "    src = np.arange(cfg['num_nodes'])\n"
        "    return src, (src + 1) % cfg['num_nodes']\n")
    (root / "bench" / "kinds" / "tally.py").write_text(
        "class Work:\n    def __init__(self, config, mix, seed, graph, devices):\n        self.graph = graph\n")
    (root / "bench" / "mixes" / "quick.json").write_text(json.dumps(
        dict(json.loads((root / "bench" / "mixes" / "front.json").read_text()),
             grid={"algorithms": ["bfs", "pagerank"], "partitioners": ["powerlaw"],
                   "placements": ["greedy"], "topologies": ["mesh2d"], "parts": [4]})))
    (root / "bench" / "limits" / "ring.quick.json").write_text(
        (root / "bench" / "limits" / "pokec.front.json").read_text())
    (root / "bench" / "metrics" / "units_done.py").write_text(
        "def read(ctx):\n    return float(len(ctx.units))\n")
    bm["configs"].append({"name": "ring", "source": "https://example.org/ring",
                          "file": "bench/configs/ring.json", "reduced": [], "why": "added as files"})
    bm["workloads"].append({"name": "ring.quick", "config": "ring", "traffic": "quick",
                            "chips": 1, "why": "added as files"})
    bm["per_layer"].append({"name": "units_done", "unit": "units", "better": "higher",
                            "source": "host_clock", "layer": "harness", "moves": "answer_s",
                            "workloads": ["ring.quick"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return str(root)


def test_every_named_piece_of_the_committed_cells_exists():
    bm = common.load_benchmark()
    for cell in bm["workloads"]:
        cfg = common.config(bm, cell)
        assert cfg["name"] == cell["config"]
        assert callable(common._module("generators", cfg["generator"], common.BENCH).edges)
        assert callable(common.work_class(common.mix(cell)["kind"]))
        assert common.limits(cell["name"])
    for metric in bm["end_to_end"] + bm["per_layer"]:
        assert callable(common.reader(metric["name"]))


def test_pieces_added_as_files_are_found_by_name(tmp_path):
    root = _checkout_with_an_added_cell(tmp_path)
    bench = os.path.join(root, "bench")
    bm = common.load_benchmark(root)
    cell = common.cell(bm, "ring.quick")
    cfg = common.config(bm, cell, root=root)
    graph = common.make_graph(cfg, 7, bench=bench)
    assert graph.num_nodes == graph.num_edges == 1000 and graph.dst[-1] == 0
    assert common.work_class("tally", bench=bench)(cfg, {}, 7, graph, []).graph is graph
    assert common.mix(cell, bench=bench)["grid"]["parts"] == [4]
    assert "bfs_mismatch" in common.limits("ring.quick", bench=bench)
    assert [m["name"] for m in common.metrics_of(bm, "ring.quick", "per_layer")] == ["units_done"]
    assert common.reader("units_done", bench=bench)(type("Ctx", (), {"units": [1, 2]})) == 2.0
    # the cells that were there keep their metrics
    assert "units_done" not in [m["name"] for m in common.metrics_of(bm, "pokec.front", "per_layer")]
