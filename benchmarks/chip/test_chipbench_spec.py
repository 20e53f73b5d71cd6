"""BENCHMARK.json against the contract, and the harness driven by data
(no chip)."""
import hashlib
import json
import os
import re

import pytest

import chipbench_fixture
import harness

REPO = chipbench_fixture.REPO
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_keys_and_limits():
    assert set(BENCH) == KEYS["top"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
    for kind in ("config", "workload", "end_to_end", "per_layer"):
        for e in BENCH[kind if kind in ("end_to_end", "per_layer")
                       else kind + "s"]:
            extra = set(e) - KEYS[kind] - {"workloads"}
            assert not extra and KEYS[kind] <= set(e), (kind, e["name"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536


def test_names_units_and_text():
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    texts = ([w["why"] for w in BENCH["workloads"]]
             + [c["why"] for c in BENCH["configs"]]
             + [c["source"] for c in BENCH["configs"]]
             + [m["layer"] for m in BENCH["per_layer"]] + BENCH["command"])
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[kind]]
        assert len(names) == len(set(names)), kind
    e2e_and_layer = [m["name"] for m in BENCH["end_to_end"]
                     + BENCH["per_layer"]]
    assert len(e2e_and_layer) == len(set(e2e_and_layer))


@pytest.mark.parametrize("cell", CELLS)
def test_workload_resolves_to_its_files(cell):
    spec = harness.resolve(REPO, cell)
    assert spec.config["name"] == spec.workload["config"]
    assert spec.dir == harness.HERE
    assert os.path.isfile(os.path.join(spec.dir, "drivers",
                                       spec.traffic["driver"] + ".py"))
    assert spec.generator(spec.config["generator"]).points
    assert spec.driver().Cell
    names = {m["name"] for m in spec.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert spec.per_layer
    for m in spec.per_layer:
        assert m["moves"] in names, (cell, m["name"])
        assert callable(spec.metric_reader(m["name"]).read)
    listed = next(c for c in BENCH["configs"]
                  if c["name"] == spec.workload["config"])
    assert listed["reduced"] == spec.config["reduced"]
    assert listed["file"].startswith(BENCH["paths"][0] + "/")


def test_every_per_layer_metric_lists_its_cells():
    """The harness reports a per-layer metric in the cells it lists; a
    metric without the list would be reported in every cell."""
    cells = set(CELLS)
    e2e = {m["name"]: set(m.get("workloads", CELLS))
           for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["workloads"] and set(m["workloads"]) <= cells, m["name"]
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]


def test_every_config_is_used_by_a_cell():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def _digests(root):
    out = {}
    for base, _, files in os.walk(os.path.join(root, "benchmarks", "chip")):
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_cell_is_new_files_and_entries_only(tmp_path):
    """A throwaway configuration, traffic mix and per-layer metric, added
    as files plus BENCHMARK.json entries, run with no other edit."""
    root = chipbench_fixture.tiny_root(tmp_path)
    chip = os.path.join(root, "benchmarks", "chip")
    before = _digests(root)
    with open(os.path.join(chip, "configs", "tiny3d.json"), "w") as f:
        json.dump({"name": "tiny3d", "generator": "uniform_box",
                   "n_points": 1500, "dims": 3, "lo": 0.0, "hi": 10.0,
                   "eps": 1.0, "join_cut": 1, "reduced": []}, f)
    with open(os.path.join(chip, "traffic", "join_once.json"), "w") as f:
        json.dump({"driver": "join"}, f)
    with open(os.path.join(chip, "metrics", "joins_seen.tiny.py"),
              "w") as f:
        f.write("def read(ctx):\n    return float(ctx['stats']['joins'])\n")
    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny3d", "source": "test",
                             "file": "benchmarks/chip/configs/tiny3d.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny3d.join", "config": "tiny3d",
                               "traffic": "join_once", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0]["workloads"].append("tiny3d.join")
    bench["per_layer"].append({"name": "joins_seen.tiny", "unit": "joins",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "join_s",
                               "workloads": ["tiny3d.join"]})
    with open(bench_path, "w") as f:
        json.dump(bench, f)
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == before
    out = harness.run(root, "tiny3d.join", 3, 0.5, True,
                      require_tpu=False, compile_cache=False)
    assert out["correct"], out["checks"]
    assert out["metrics"]["joins_seen.tiny"]["value"] >= 1
    assert "build_ms.join" not in out["metrics"]   # lists its own cells
    assert list(out)[-1] == "checks"
