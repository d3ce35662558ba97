"""BENCHMARK.json against the rules the harness and its checks rely on:
names and units, where every file lies, which cell reports which
metric, and the time a full check of 24 cells would take."""

from __future__ import annotations

import json
import pathlib
import re

import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import run  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = {w["name"]: w for w in SPEC["workloads"]}
E2E = {m["name"]: m for m in SPEC["end_to_end"]}
WIDTH = re.compile(r"^(hidden_size|.*intermediate_size|.*_dim|.*_rank|"
                   r".*latent.*|.*state_size|.*proj.*|.*expan.*|"
                   r"num_experts_per_tok|num_attention_heads|"
                   r"num_key_value_heads)$")


def reports(cell: str, metric: dict) -> bool:
    return cell in metric.get("workloads", [cell])


def one_line(text: str, limit: int = 200) -> bool:
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["command"]) <= 32
    assert all(one_line(w) for w in SPEC["command"])
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    for word in SPEC["command"][1:]:
        if word.endswith(".py"):
            assert not word.startswith("/") and ".." not in word
            assert any(word.startswith(p + "/") for p in SPEC["paths"])
            assert (ROOT / word).is_file()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 << 10


def test_names_units_and_entry_keys():
    names = ([c["name"] for c in SPEC["configs"]]
             + list(CELLS) + list(E2E)
             + [m["name"] for m in SPEC["per_layer"]])
    for kind in (SPEC["configs"], SPEC["workloads"],
                 SPEC["end_to_end"] + SPEC["per_layer"]):
        got = [e["name"] for e in kind]
        assert len(got) == len(set(got)), got
    for n in names:
        assert NAME.match(n), n
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert one_line(m["layer"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_files_are_found_by_name():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/configs/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert (ROOT / "bench" / "configs" / f"{c['name']}_ref.py").is_file()
        sizes = cfg.get("model", cfg.get("data", {}))
        for key in c["reduced"]:
            assert key in sizes and not WIDTH.search(key), key
    for w in SPEC["workloads"]:
        traffic = json.loads(
            (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench" / "drivers" / f"{traffic['kind']}.py").is_file()
    for m in SPEC["per_layer"]:
        assert run.reader_file(m["name"], ROOT).is_file(), m["name"]


def test_a_metric_split_by_cell_is_read_by_the_file_of_its_stem(tmp_path):
    metrics = tmp_path / "bench" / "metrics"
    metrics.mkdir(parents=True)
    for name in ("device_idle", "device_idle.train", "a.b"):
        (metrics / f"{name}.py").write_text("")
    assert run.reader_file("device_idle.scan", tmp_path).name == \
        "device_idle.py"
    assert run.reader_file("device_idle.train", tmp_path).name == \
        "device_idle.train.py"
    assert run.reader_file("a.b.c.d", tmp_path).name == "a.b.py"
    assert not run.reader_file("missing.x", tmp_path).is_file()


def test_every_config_and_cell_is_covered():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(CELLS) // 2)
    for cell in CELLS:
        e2e = [m["name"] for m in SPEC["end_to_end"] if reports(cell, m)]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert any(reports(cell, m) for m in SPEC["per_layer"]), cell


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_moves_target_is_reported_where_the_metric_is(metric):
    target = E2E[metric["moves"]]
    for cell in metric.get("workloads", list(CELLS)):
        assert cell in CELLS
        assert reports(cell, target), (metric["name"], cell)


def test_bounds_and_run_length_fit_a_full_check():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
