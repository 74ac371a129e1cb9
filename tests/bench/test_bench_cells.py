"""Every cell of BENCHMARK.json resolves, by file name alone, to its
configuration, its reference, its traffic mix, its limit and a reader for
each metric it reports."""
import json
import re

import pytest

from bench import ROOT, cells, check, run

BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_from_files(workload):
    cell = cells.resolve(workload, BENCH)
    entry = {c["name"]: c for c in BENCH["configs"]}[
        {w["name"]: w for w in BENCH["workloads"]}[workload]["config"]]
    assert cell.config["name"] == entry["name"]
    assert (ROOT / entry["file"]).resolve() == \
        (cells.CONFIG_DIR / f"{entry['name']}.json").resolve()
    assert check.reference_module(cell.config).hidden_states
    assert cell.mix["kind"] == "fan_out"
    assert run.limits(workload)
    names = {m["name"] for m in cell.end_to_end}
    assert {"setup_s"} < names
    assert cell.per_layer
    for trace in (False, True):
        for name, reader in cell.readers(trace).items():
            assert callable(reader.read), name


def test_benchmark_file_keeps_to_its_shapes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells_ = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells_)) <= cells_
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for path in BENCH["paths"]:
        assert (ROOT / path).is_dir()


def test_peaks_name_their_source():
    table = json.loads(run.PEAKS.read_text())
    assert table["source"]
    assert run.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.peaks("cpu")
