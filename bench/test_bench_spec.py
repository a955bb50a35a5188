"""BENCHMARK.json and the files under bench/ keep the benchmark's
contract: legal names, every cell's files found by name, the keys each
entry may have."""
import json
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_./-]{1,200}")
FILE_CHARS = re.compile(r"[A-Za-z0-9_./-]+")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.fullmatch(p) and not p.startswith("/") and ".." not in p
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for w in cmd:
        if "/" in w:
            assert any(w.startswith(p + "/") for p in SPEC["paths"])
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_check_fits_the_time_allowed():
    cells = 24                        # what later PRs may grow to
    runs = 2 + 14 * cells
    total = runs * (SPEC["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200


def test_names_are_legal_and_unique():
    groups = [SPEC["configs"], SPEC["workloads"],
              SPEC["end_to_end"] + SPEC["per_layer"]]
    for g in groups:
        names = [e["name"] for e in g]
        assert len(names) == len(set(names))
        assert all(NAME.fullmatch(n) for n in names)


def test_configs():
    assert 1 <= len(SPEC["configs"]) <= 24
    used = {w["config"] for w in SPEC["workloads"]}
    files = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["why"])
        assert c["name"] in used
        assert _line(c["source"])
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.fullmatch(k)
            assert not re.search(r"(_dim|_rank|size|width)$", k)
            assert k in body            # the value as it is run
        assert (BENCH / "configs" / f"{c['name']}.py").is_file()
        assert (BENCH / "systems" / f"{body['system']}.py").is_file()


def test_workloads_resolve_by_name():
    pairs = set()
    four = 0
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        assert _line(w["why"])
        assert NAME.fullmatch(w["traffic"]) and NAME.fullmatch(w["config"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        body = json.loads((BENCH / "workloads" /
                           f"{w['name']}.json").read_text())
        assert body["config"] == w["config"]
        assert body["traffic"] == w["traffic"]
        mix = json.loads((BENCH / "traffic" /
                          f"{w['traffic']}.json").read_text())
        assert body["chips"] == w["chips"]
        assert body["why"] == w["why"]
        assert body["limits"]
        assert (BENCH / "traffic" / f"{mix['kind']}.py").is_file()
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_metric_entries(group):
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC[group]:
        keys = {"name", "unit", "better", "source"}
        keys |= {"bound"} if group == "end_to_end" else {"layer", "moves"}
        assert set(m) - {"workloads"} == keys
        assert UNIT.fullmatch(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
        if group == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert _line(m["layer"])
            mv = e2e[m["moves"]]
            assert set(m["workloads"]) <= set(mv.get("workloads", cells))
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
            if m["name"].endswith("_roofline") or "mfu" in m["name"]:
                assert m["unit"] == "%"


def test_every_cell_reports_setup_another_metric_and_a_layer():
    e2e_all = [m for m in SPEC["end_to_end"]]
    assert any(m["name"] == "setup_s" for m in e2e_all)
    for w in SPEC["workloads"]:
        mine = [m["name"] for m in e2e_all
                if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in mine and len(mine) >= 2
        layers = [m for m in SPEC["per_layer"]
                  if w["name"] in m.get("workloads", [w["name"]])]
        assert layers


def test_files_under_paths_are_named_from_name_characters():
    for p in SPEC["paths"]:
        for f in (ROOT / p).rglob("*"):
            rel = f.relative_to(ROOT).as_posix()
            if "__pycache__" in rel or "/." in rel:
                continue
            assert FILE_CHARS.fullmatch(rel), rel
