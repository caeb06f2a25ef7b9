"""BENCHMARK.json as the harness reads it: every cell finds its files,
and every metric finds its reader and a cell that reports it, so a new
cell or family comes in as new files and entries alone."""
import os

import pytest

import families
import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = run.load_json("BENCHMARK.json")
WHOLE_STEP = ("step.mfu", "step.hbm_bw_share", "device.idle_share")


def test_whole_step_shares_reach_any_cell():
    """A cell that no metric's list names still gets the shares of the
    whole step."""
    got = {m["name"] for m in run.for_cell("granite.chat", SPEC["per_layer"])}
    assert set(WHOLE_STEP) <= got


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_finds_its_files(cell):
    conf = {c["name"]: c for c in SPEC["configs"]}[cell["config"]]
    config = run.load_json(conf["file"])
    for entry in config["models"].values():
        families.of(entry)
        assert os.path.exists(os.path.join(
            BENCH, "reference", f"{entry['family']}.py"))
    traffic = run.load_json(f"bench/traffic/{cell['traffic']}.json")
    assert os.path.exists(os.path.join(BENCH, "generators",
                                       f"{traffic['generator']}.py"))


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_reports_what_its_metrics_move(cell):
    """Every per-layer metric of a cell moves an end-to-end metric the
    cell reports, and every metric has its reader."""
    e2e = {m["name"] for m in run.for_cell(cell["name"], SPEC["end_to_end"])}
    assert "setup_s" in e2e and len(e2e) > 1
    per_layer = run.for_cell(cell["name"], SPEC["per_layer"])
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e, m["name"]
    for m in per_layer + SPEC["end_to_end"]:
        if m["name"] != "setup_s":
            run.metric_reader(m["name"])
