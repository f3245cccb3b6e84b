"""BENCHMARK.json against the contract, and the harness finding a cell that
is dropped in as files."""
import json
import re
import shutil

import pytest

from perfbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = json.loads((harness.REPO / "BENCHMARK.json").read_text())


def test_keys_names_and_units():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (harness.REPO / c["file"]).is_file()
        names += [c["name"]] + c["reduced"]
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for n in names:
        assert NAME.match(n), n
    assert len({m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]}) \
        == len(SPEC["end_to_end"]) + len(SPEC["per_layer"])


def test_every_cell_reports_what_the_contract_asks():
    e2e = SPEC["end_to_end"]
    for w in SPEC["workloads"]:
        ld = harness.load_cell(w["name"])
        got = {m["name"] for m in ld["end_to_end"]}
        assert "setup_s" in got and len(got) >= 2, w["name"]
        assert ld["per_layer"], w["name"]
        for m in ld["per_layer"]:
            assert m["moves"] in got, (w["name"], m["name"])
            assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
    for m in e2e + SPEC["per_layer"]:
        for c in m.get("workloads", []):
            assert c in {w["name"] for w in SPEC["workloads"]}, m["name"]


def test_a_cell_dropped_in_as_files_is_found(tmp_path, monkeypatch):
    bench = tmp_path / "perfbench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append(dict(name="internlm2.new",
                                  config="internlm2-1.8b",
                                  traffic="new_mix", chips=1, why="test"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    mix = json.loads((bench / "traffic" / "decode_sat.json").read_text())
    (bench / "traffic" / "new_mix.json").write_text(
        json.dumps(dict(mix, output=dict(dist="uniform", lo=8, hi=16))))
    (bench / "limits" / "internlm2.new.json").write_text(
        '{"logit_gap_mean": 1e-3}')
    monkeypatch.setattr(harness, "REPO", tmp_path)
    monkeypatch.setattr(harness, "BENCH", bench)
    ld = harness.load_cell("internlm2.new")
    assert ld["traffic"]["output"]["hi"] == 16
    assert ld["limits"]["logit_gap_mean"] == 1e-3
    assert ld["config"]["hidden_size"] == 2048
    with pytest.raises(SystemExit):
        harness.load_cell("no.such.cell")
