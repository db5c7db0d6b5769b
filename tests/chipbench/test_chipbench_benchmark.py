"""The cells of the committed BENCHMARK.json, read as the harness reads
them."""
from __future__ import annotations

import json

from cb_helpers import REPO

from chipbench import harness


def test_the_four_chip_cell_is_vgg19_96_on_four_chips():
    """`vgg19_96_dp4.closed128` serves vgg19_96 unchanged but for its batch
    of 32, sharded 8 a chip: vgg19_96.closed32 is its one-chip control."""
    cell = harness.find_cell(REPO, "vgg19_96_dp4.closed128")
    one = harness.find_cell(REPO, "vgg19_96.closed32")
    assert cell.chips == 4 and one.chips == 1
    assert cell.cfg["serving"]["max_batch"] == 32
    assert cell.mix["buckets"] == [32] and cell.mix["warm_sizes"] == [32]
    assert cell.mix["in_flight"] == 4 * one.mix["in_flight"]
    assert {k: v for k, v in cell.mix.items() if k not in (
        "in_flight", "buckets", "warm_sizes", "about")} == {
        k: v for k, v in one.mix.items() if k not in (
            "in_flight", "buckets", "warm_sizes", "about")}
    a, b = dict(cell.cfg), dict(one.cfg)
    assert (a.pop("name"), b.pop("name")) == ("vgg19_96_dp4", "vgg19_96")
    assert a.pop("deployment") != b.pop("deployment")
    a["serving"] = dict(a["serving"], max_batch=8)
    assert a == b
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert "vgg19_96_dp4.closed128" in next(
        m for m in bench["end_to_end"] if m["name"] == "images_per_s")["workloads"]
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in bench["per_layer"]}
    assert [m["name"] for m in cell.end_to_end] == ["images_per_s", "setup_s"]
