"""``BENCHMARK.json`` against the rules its runs rely on: names and units
use only the allowed characters; every per-layer metric's cells report
the end-to-end metric it moves; every configuration has a cell; every
cell finds its files; every metric has a reader; each configuration
file agrees with its entry and with the program's registered arch."""
import json
import re

import pytest

from chipbench.cell import BENCH, ROOT, load_cell, load_manifest

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
#: Keys that give a width; ``reduced`` may never name one.
WIDTHS = re.compile(r"(hidden_size|intermediate_size|latent|state_size"
                    r"|proj|head_dim|expan|experts_per_tok|_dim$|_rank$)")

M = load_manifest()
CELLS = [w["name"] for w in M["workloads"]]


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_shape():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["command"][:2] == ["python3", "chipbench/run.py"]
    assert M["paths"] == ["chipbench"]
    rs = M["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits its 12 hours
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(M)) <= 64 * 1024


def test_names_units_and_keys():
    metrics = M["end_to_end"] + M["per_layer"]
    for group in (M["configs"], M["workloads"], metrics):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.fullmatch(n) for n in names)
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.fullmatch(w["traffic"]) and _line(w["why"])
        assert w["chips"] in (1, 4)
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert all(NAME.fullmatch(k) for k in c["reduced"])
    for m in metrics:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower",
                                                              "higher")
        assert m["source"] in SOURCES
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"])


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_every_cell_reports_what_its_layers_move():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    for m in M["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert _reports(e2e[m["moves"]], cell), (m["name"], cell)
    for cell in CELLS:
        names = [m["name"] for m in M["end_to_end"] if _reports(m, cell)]
        assert "setup_s" in names and len(names) >= 2
        assert any(_reports(m, cell) for m in M["per_layer"])
    assert "workloads" not in e2e["setup_s"]


def test_every_config_has_a_cell_and_its_file():
    used = {w["config"] for w in M["workloads"]}
    for c in M["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("chipbench/")
        with open(ROOT / c["file"]) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert not [k for k in c["reduced"] if WIDTHS.search(k)]
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))


def test_every_metric_has_a_reader():
    for m in M["end_to_end"] + M["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads(name):
    cell = load_cell(name)
    assert cell.limits
    assert (BENCH / "arrivals" / f"{cell.mix['loop']}.py").is_file()
    assert "setup_s" in [m["name"] for m in cell.end_to_end]


@pytest.mark.parametrize("entry", M["configs"], ids=lambda c: c["name"])
def test_published_widths_are_the_programs(entry):
    from repro.configs import ARCHS
    from chipbench.adapters.dense_gqa import program_spec
    with open(ROOT / entry["file"]) as f:
        cfg = json.load(f)
    spec = program_spec(cfg)
    arch = ARCHS[cfg["program"]["arch"]]
    assert (spec.d_model, spec.num_heads, spec.num_kv_heads, spec.head_dim,
            spec.d_ff, spec.vocab_size) == (
        arch.d_model, arch.num_heads, arch.num_kv_heads, arch.head_dim,
        arch.d_ff, arch.vocab_size)
    published = cfg.get("published", {})
    for key in cfg["reduced"]:
        assert key in published and published[key] != cfg[key]
