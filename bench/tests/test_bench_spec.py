"""BENCHMARK.json against the contract's shape, and the harness finding
every file by name (CPU, no accelerator)."""

import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, load, peaks  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark(ROOT)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
    for word in bench["command"]:
        assert TEXT.match(word) and not word.startswith("/")
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_entries_have_exactly_their_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_names_units_and_text(bench):
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[kind]:
            assert NAME.match(e["name"]), e["name"]
            names.append((kind, e["name"]))
            for k in ("why", "layer", "source"):
                if k in e and kind != "end_to_end" and kind != "per_layer":
                    assert TEXT.match(e[k]), (e["name"], k)
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            if kind == "per_layer":
                assert TEXT.match(e["layer"])
    assert len(names) == len(set(names))
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in bench["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])


def test_every_name_resolves_to_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        cfg, mod = harness.load_config(ROOT, c["name"])
        assert set(c["reduced"]) <= set(cfg["reduced"])
        assert hasattr(mod, "build")
    pairs = set()
    for w in bench["workloads"]:
        assert w["config"] in configs
        load.load_mix(os.path.join(ROOT, "bench", "traffic",
                                   w["traffic"] + ".json"))
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    for m in bench["per_layer"] + bench["end_to_end"]:
        if m["name"] != "setup_s":
            assert callable(harness.load_metric(ROOT, m["name"]).read)


def test_every_cell_reports_what_it_must(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    cells = [w["name"] for w in bench["workloads"]]
    for cell in cells:
        mine = {m["name"] for m in harness.metrics_of(bench, cell,
                                                      "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        assert harness.metrics_of(bench, cell, "per_layer")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", cells)


def test_peaks_resolve_and_unknown_devices_raise():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v99")


def test_a_cell_is_added_by_new_files_alone(tmp_path, monkeypatch):
    """A new configuration, traffic mix and per-layer metric: new files and
    new BENCHMARK.json entries only; the harness runs the new cell (on the
    CPU, at a tiny size) and no file it already had changes."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()
              and p.name != "BENCHMARK.json"}

    cfgs = root / "bench" / "configs"
    cfg = json.loads((cfgs / "uq2_sf1.json").read_text())
    cfg.update(name="uq2n_sf1", selections={"UQ2_JN": [["p_size", "<=", 40]],
                                            "UQ2_JX": [["p_size", ">=", 30]]})
    (cfgs / "uq2n_sf1.json").write_text(json.dumps(cfg))
    (cfgs / "uq2n_sf1.py").write_text((cfgs / "uq2_sf1.py").read_text())
    (root / "bench" / "traffic" / "closed2x1024.json").write_text(json.dumps(
        {"kind": "closed", "clients": 2, "sizes": {"fixed": 1024}}))
    (root / "bench" / "metrics" / "requests.new.py").write_text(
        "def read(ctx):\n    return len(ctx['requests'])\n")

    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][1], name="uq2n_sf1",
                                 file="bench/configs/uq2n_sf1.json"))
    bench["workloads"].append({"name": "uq2n_sf1.small", "config": "uq2n_sf1",
                               "traffic": "closed2x1024", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "samples_per_s" and "workloads" in m:
            m["workloads"].append("uq2n_sf1.small")
    bench["per_layer"].append({"name": "requests.new", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "load generator",
                               "moves": "samples_per_s",
                               "workloads": ["uq2n_sf1.small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    monkeypatch.setenv("REPRO_OBS_TRACE", "0")   # a traced run sets it
    cell = harness.find_cell(bench, "uq2n_sf1.small")
    small = {"scale_factor": 0.002, "round_batch": 1024}
    for trace in (False, True):
        out, numbers = harness.run_cell(str(root), bench, cell, seed=5,
                                        seconds=1.0, trace=trace,
                                        t_start=0.0, chip=False,
                                        config_override=small)
        assert out["correct"], numbers
        want = "requests.new" if trace else "samples_per_s"
        assert want in out["metrics"]
    after = {p: p.read_bytes() for p in before}
    assert after == before
