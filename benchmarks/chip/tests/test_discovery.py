"""A new traffic mix and a new per-layer metric are found by name, with
no edit to a file that is there."""
import json
import os
import shutil

import spec


def test_new_traffic_and_metric_files_are_picked_up(tmp_path, monkeypatch):
    copy = tmp_path / "chip"
    shutil.copytree(spec.HERE, copy,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: (copy / p).read_bytes() for p in
              [os.path.relpath(os.path.join(d, f), copy)
               for d, _, fs in os.walk(copy) for f in fs]}
    t = json.loads((copy / "traffic" / "width.m3.json").read_text())
    t["local_steps"] = 5
    (copy / "traffic" / "width.m3.steps5.json").write_text(json.dumps(t))
    (copy / "metrics" / "rounds_in_window.py").write_text(
        "def read(ctx):\n    return ctx['window']['rounds']\n")
    monkeypatch.setattr(spec, "HERE", str(copy))

    assert spec.traffic("width.m3.steps5")["local_steps"] == 5
    bench = {"workloads": [{"name": "a.b", "config": "smollm-135m",
                            "traffic": "width.m3.steps5", "chips": 1}],
             "per_layer": [{"name": "rounds_in_window", "unit": "rounds"},
                           {"name": "elsewhere", "unit": "ms",
                            "workloads": ["other.cell"]}]}
    names = [m["name"] for m in spec.cell_metrics(bench, "a.b", "per_layer")]
    assert names == ["rounds_in_window"]
    assert spec.reader("rounds_in_window")({"window": {"rounds": 7}}) == 7
    after = {p: (copy / p).read_bytes() for p in before}
    assert after == before
