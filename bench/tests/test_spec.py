"""BENCHMARK.json resolves to files found by name, and a new cell, mix,
metric and architecture placed as new files are picked up with no
existing file edited."""
import json
import re
import shutil
import time
from pathlib import Path

import pytest

from harness import compare, main, readings

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_spec_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in SPEC[k]}) == len(SPEC[k])
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves(cell):
    r = main.resolve(SPEC, cell, ROOT)
    assert r["config"]["name"] == r["cell"]["config"]
    assert r["mix"]["seq_len"] % 128 == 0
    assert {m["name"] for m in r["end_to_end"]} >= {"setup_s"}
    assert len(r["end_to_end"]) >= 2 and r["per_layer"]
    assert (ROOT / "bench" / "limits" / f"{cell}.json").is_file()
    cfg = next(c for c in SPEC["configs"] if c["name"] == r["cell"]["config"])
    assert set(cfg["reduced"]) == set(r["config"]["reduced"])
    assert cfg["source"] == r["config"]["source"]


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(readings.reader(metric).read)


def test_new_cell_mix_and_metric_are_picked_up_from_new_files(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / spec["configs"][0]["file"]).read_text())
    cfg["name"] = "new-model"
    (tmp_path / "bench/configs/new-model.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/new-mix.json").write_text(json.dumps(
        {"distribution": "pretrain", "seq_len": 1024, "max_doc_len": 1024,
         "rows_per_rank": 2, "layouts": 2, "layout_seed": 1}))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec["configs"].append({"name": "new-model", "source": cfg["source"],
                            "file": "bench/configs/new-model.json",
                            "reduced": cfg["reduced"], "why": "test"})
    spec["workloads"].append({"name": "new-model.new-mix",
                              "config": "new-model", "traffic": "new-mix",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "new_metric", "unit": "%",
                              "better": "higher", "source": "program_counter",
                              "layer": "test", "moves": "setup_s",
                              "workloads": ["new-model.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    r = main.resolve(spec, "new-model.new-mix", tmp_path)
    assert r["mix"]["seq_len"] == 1024 and r["config"]["name"] == "new-model"
    assert "new_metric" in {m["name"] for m in r["per_layer"]}
    old = main.resolve(spec, spec["workloads"][0]["name"], tmp_path)
    assert "new_metric" not in {m["name"] for m in old["per_layer"]}
    reader = readings.METRICS
    try:
        readings.METRICS = tmp_path / "bench" / "metrics"
        assert readings.reader("new_metric").read(None) == 42.0
    finally:
        readings.METRICS = reader
    assert all(p.read_bytes() == b for p, b in before.items())


NEW_ARCH = Path(__file__).resolve().parent / "data" / "windowed"


def _windowless(monkeypatch):
    """The program's window removed: its local layers run as global."""
    import repro.models.model as M
    real = M.block_apply

    def block_apply(kind, *args, **kw):
        return real("global" if kind == "local" else kind, *args, **kw)
    monkeypatch.setattr(M, "block_apply", block_apply)


def test_new_architecture_runs_from_new_files(tmp_path, monkeypatch):
    """A decoder whose layers alternate a 64-token window and full
    attention, with its own reference module, configuration, mix and
    limits, all new files in a copy of the benchmark: it runs correct on
    the CPU through the untouched harness, half of its layers go to the
    CA servers, and the program without its window is not correct."""
    new = [f.relative_to(NEW_ARCH) for f in NEW_ARCH.rglob("*")
           if f.suffix in (".py", ".json")]
    assert len(new) == 4 and not any((ROOT / "bench" / f).exists()
                                     for f in new)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copytree(NEW_ARCH, tmp_path / "bench", dirs_exist_ok=True)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((NEW_ARCH / "configs" /
                      "windowed-tiny.json").read_text())
    spec["configs"].append({"name": cfg["name"], "source": cfg["source"],
                            "file": "bench/configs/windowed-tiny.json",
                            "reduced": cfg["reduced"], "why": "test"})
    cell = "windowed-tiny.tiny-256"
    spec["workloads"].append({"name": cell, "config": cfg["name"],
                              "traffic": "tiny-256", "chips": 1,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    r = main.resolve(spec, cell, tmp_path)
    monkeypatch.setattr(compare, "BENCH", tmp_path / "bench")
    ref = compare.reference_module(r["config"])
    assert (ref.ca_layers(r["config"]),
            r["config"]["num_hidden_layers"]) == (1, 2)

    def run():
        return main.run_cell(r, 2 ** 31 + 20251019, 0.3, False,
                             t_start=time.perf_counter(),
                             limits=compare.limits(cell), chips=1,
                             allow_cpu=True)

    out = run()
    assert out["correct"], out["checks"]
    _windowless(monkeypatch)
    out = run()
    assert not out["correct"], out["checks"]

    def sources(bench):
        return {p.relative_to(bench): p.read_bytes()
                for p in (bench / "harness").rglob("*.py")}
    assert sources(tmp_path / "bench") == sources(ROOT / "bench")
