"""Discovery by name from BENCHMARK.json, the peaks table and the byte
count of one executed step."""

import json
import re

import pytest

from bench import harness
from bench.roofline import peaks, step_bytes

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def test_manifest_keys_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in manifest["configs"]]
             + [w["name"] for w in manifest["workloads"]]
             + [m["name"] for m in manifest["end_to_end"]]
             + [m["name"] for m in manifest["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(
        1, len(manifest["workloads"]) // 2)


CELLS = [w["name"] for w in harness.load_manifest()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_finds_its_files_and_readers(manifest, cell):
    w, config, traffic = harness.cell_files(manifest, cell)
    assert config["name"] == w["config"]
    e2e = harness.cell_metrics(manifest, cell, traced=False)
    layer = harness.cell_metrics(manifest, cell, traced=True)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert layer and {m["moves"] for m in layer} <= {m["name"] for m in e2e}
    for m in e2e + layer:
        assert callable(harness.reader(m["name"]).read)


def test_per_layer_metrics_name_one_reported_e2e_metric(manifest):
    for m in manifest["per_layer"]:
        for cell in m["workloads"]:
            e2e = harness.cell_metrics(manifest, cell, traced=False)
            assert m["moves"] in {e["name"] for e in e2e}, m["name"]


def test_reader_is_the_longest_module_prefix():
    assert harness.reader("steps_per_kcycle.sweep").__name__ == \
        "bench.metrics.steps_per_kcycle"
    with pytest.raises(KeyError):
        harness.reader("no_such_metric.l1")


def test_unknown_workload_is_an_error(manifest):
    with pytest.raises(KeyError):
        harness.cell_files(manifest, "ddr5.nothing")


def test_peaks_know_the_v5e_and_refuse_an_unknown_kind():
    v5e = peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and "Google Cloud" in v5e["source"]
    with pytest.raises(ValueError, match="no peaks"):
        peaks("TPU v9 imaginary")


def test_step_bytes_counts_state_from_the_configuration(manifest):
    _, config, _ = harness.cell_files(manifest, "ddr4-2ch.decode.l1")
    # 64 banks x (10 state + 6 queue head) + 4 ranks x 7 timing words
    # + 64 x 4 response-queue words + 2, each read and written once
    assert step_bytes(config, 1) == 2 * 4 * (64 * 16 + 4 * 7 + 64 * 4 + 2)
    assert step_bytes(config, 64) == 64 * step_bytes(config, 1)


def test_config_files_state_every_reduced_key(manifest):
    for c in manifest["configs"]:
        doc = json.loads(open(c["file"]).read())
        assert set(c["reduced"]) == set(doc["reduced"]), c["name"]
        for k in c["reduced"]:
            assert k in doc


TRAFFIC = sorted(p.stem for p in (harness.BENCH / "traffic").glob("*.json"))


@pytest.mark.parametrize("name", TRAFFIC)
def test_each_traffic_file_finds_its_entry_by_module_name(name):
    from bench import entries

    doc = json.loads((harness.BENCH / "traffic" / f"{name}.json").read_text())
    cls = entries.get(doc["entry"])
    assert issubclass(cls, entries.Entry)
    assert cls.__module__ == f"bench.entries.{doc['entry']}"


def test_unknown_entry_is_an_error():
    from bench import entries

    with pytest.raises(ModuleNotFoundError):
        entries.get("no_such_entry")


def test_cxl_tier_is_dram_plus_the_configured_adder(manifest):
    from bench import entries

    _, config, traffic = harness.cell_files(manifest, "ddr4-cxl.serve.l8")
    rp = entries.get(traffic["entry"])(config, traffic).params
    add = config["cxl_tier"]["add"]
    for field in rp._fields:
        dram, cxl = (int(x) for x in getattr(rp, field))
        assert cxl - dram == add.get(field, 0), field
