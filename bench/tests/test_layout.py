"""Every cell resolves to its files by name, and a new cell is found from
new files alone."""
import filecmp
import json
import os
import shutil

from layout import Layout

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_every_cell_resolves():
    lay = Layout(ROOT)
    spec = lay.spec
    assert {c["name"] for c in spec["configs"]} == {
        w["config"] for w in spec["workloads"]}
    for w in spec["workloads"]:
        cfg = lay.config(w["config"])
        assert cfg["name"] == w["config"]
        traffic = lay.traffic(w["traffic"])
        assert traffic["ordering"] in ("grab", "cd-grab", "rr")
        limits = lay.limits(w["name"])
        assert limits and all(v >= 0 for v in limits.values())
        for kind in ("end_to_end", "per_layer"):
            for m in lay.metrics_for(w["name"], kind):
                assert callable(lay.reader(m["name"]))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(lay.reader(m["name"]))


ENTRY_KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}


def test_every_entry_has_exactly_its_keys():
    spec = Layout(ROOT).spec
    assert set(spec) == {"command", "paths", "run_seconds", *ENTRY_KEYS}
    for kind, (required, optional) in ENTRY_KEYS.items():
        for entry in spec[kind]:
            assert required <= set(entry) <= required | optional, (kind,
                                                                   entry)
    for c in spec["configs"]:
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]


def test_new_cell_is_found_without_editing_a_file(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".runs",
                                                  "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    before = tmp_path / "before"
    shutil.copytree(tmp_path / "bench", before)

    bench = tmp_path / "bench"
    cfg = json.loads((bench / "configs" / "phi3-mini-3.8b.1chip.json")
                     .read_text())
    cfg["name"] = "phi3-mini-3.8b.1chip-l1"
    cfg["num_hidden_layers"] = 1
    (bench / "configs" / "phi3-mini-3.8b.1chip-l1.json").write_text(
        json.dumps(cfg))
    traffic = json.loads((bench / "traffic" / "grab.s512.json").read_text())
    traffic["seq_len"] = 1024
    (bench / "traffic" / "grab.s1k.json").write_text(json.dumps(traffic))
    (bench / "limits" / "phi3-l1.grab.s1k.json").write_text(
        json.dumps({"loss_gap": 1.0}))
    (bench / "metrics" / "step.new_ms.py").write_text(
        "def read(run):\n    return 1.5\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": cfg["name"], "source": "x",
        "file": "bench/configs/phi3-mini-3.8b.1chip-l1.json",
        "reduced": ["num_hidden_layers"], "why": "x"})
    spec["workloads"].append({"name": "phi3-l1.grab.s1k",
                              "config": cfg["name"], "traffic": "grab.s1k",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "step.new_ms", "unit": "ms",
                              "better": "lower", "source": "device_trace",
                              "layer": "Step", "moves": "tokens_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    lay = Layout(str(tmp_path))
    w = lay.cell("phi3-l1.grab.s1k")
    assert lay.config(w["config"])["num_hidden_layers"] == 1
    assert lay.traffic(w["traffic"])["seq_len"] == 1024
    assert lay.limits(w["name"]) == {"loss_gap": 1.0}
    names = [m["name"] for m in lay.metrics_for(w["name"], "per_layer")]
    assert "step.new_ms" in names and "grab.state_gib" not in names
    assert lay.reader("step.new_ms")({}) == 1.5
    # the files that were there are unchanged
    cmp = filecmp.dircmp(before, bench, ignore=["__pycache__"])
    changed = []

    def walk(d):
        changed.extend(d.diff_files + d.left_only)
        for sub in d.subdirs.values():
            walk(sub)

    walk(cmp)
    assert changed == []
