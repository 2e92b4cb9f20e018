"""Every name in BENCHMARK.json, and every generator and driver its
files name, resolves to its file under bench/."""

import pytest

from harness import registry

BENCH = registry.benchmark()


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_finds_config_and_traffic(w):
    assert w["name"] == f"{w['config']}.{w['traffic']}"
    cfg = registry.config(w["config"])
    assert cfg["name"] == w["config"]
    assert callable(registry.generator(cfg["gen"]).instance)
    driver = registry.driver(registry.traffic(w["traffic"])["driver"])
    assert all(callable(getattr(driver, f))
               for f in ("call", "work", "units", "check"))


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_and_reduced_keys(c):
    cfg = registry.config(c["name"])
    assert c["file"] == f"bench/configs/{c['name']}.json"
    for key in c["reduced"]:
        assert cfg[key] != cfg[f"source_{key}"]


@pytest.mark.parametrize(
    "m", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_found(m):
    assert callable(registry.metric(m["name"]).read)


def test_every_cell_reports_setup_an_end_to_end_and_a_layer_metric():
    for w in BENCH["workloads"]:
        e2e = [m["name"] for m in registry.cell_metrics(BENCH, w["name"],
                                                        "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = registry.cell_metrics(BENCH, w["name"], "per_layer")
        assert layer and all(m["moves"] in e2e for m in layer)
