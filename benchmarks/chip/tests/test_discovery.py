"""A new traffic mix, a new per-layer metric and a new model family are
found by name, with no edit to a file that is there; the dense family
reads as it did before it moved into ``families/dense.py``."""
import json
import os
import shutil

import jax.numpy as jnp
import pytest

import spec


def _copy(tmp_path, monkeypatch):
    """A copy of the harness's files that ``spec`` reads from, and the
    bytes of every file in it."""
    copy = tmp_path / "chip"
    shutil.copytree(spec.HERE, copy,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: (copy / p).read_bytes() for p in
              [os.path.relpath(os.path.join(d, f), copy)
               for d, _, fs in os.walk(copy) for f in fs]}
    monkeypatch.setattr(spec, "HERE", str(copy))
    return copy, before


def _unchanged(copy, before):
    return {p: (copy / p).read_bytes() for p in before} == before


def test_new_traffic_and_metric_files_are_picked_up(tmp_path, monkeypatch):
    copy, before = _copy(tmp_path, monkeypatch)
    t = json.loads((copy / "traffic" / "width.m3.json").read_text())
    t["local_steps"] = 5
    (copy / "traffic" / "width.m3.steps5.json").write_text(json.dumps(t))
    (copy / "metrics" / "rounds_in_window.py").write_text(
        "def read(ctx):\n    return ctx['window']['rounds']\n")

    assert spec.traffic("width.m3.steps5")["local_steps"] == 5
    bench = {"workloads": [{"name": "a.b", "config": "smollm-135m",
                            "traffic": "width.m3.steps5", "chips": 1}],
             "per_layer": [{"name": "rounds_in_window", "unit": "rounds"},
                           {"name": "elsewhere", "unit": "ms",
                            "workloads": ["other.cell"]}]}
    names = [m["name"] for m in spec.cell_metrics(bench, "a.b", "per_layer")]
    assert names == ["rounds_in_window"]
    assert spec.reader("rounds_in_window")({"window": {"rounds": 7}}) == 7
    assert _unchanged(copy, before)


TOY_FAMILY = '''
def program_config(cfg, pcfg):
    return pcfg.replace(d_model=cfg["hidden_size"])


def shapes(cfg):
    D, V, R = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    return {"embed": (V, D), "stages": (({"mix": {"w": (R, D, D)}},),)}


def axis_sizes(cfg, leaf, width):
    trail = leaf.shape[1:] if leaf.stacked else leaf.shape
    return tuple(max(1, int(width * n)) for n in trail)


def loss(p, tokens, cfg, sizes):
    return 1000.0 * len(sizes) + tokens.sum()


def train_flops(cfg, width, depths, batch, seq):
    return 7.0 * width * sum(depths) * batch * seq
'''


def test_new_family_file_is_picked_up(tmp_path, monkeypatch):
    import harness
    import reference
    import weights
    import work
    copy, before = _copy(tmp_path, monkeypatch)
    (copy / "families" / "toy.py").write_text(TOY_FAMILY)
    cfg = json.loads((copy / "configs" / "smollm-135m.json").read_text())
    cfg = {"name": "toy", "family": "toy", "registry": "smollm-135m",
           "num_hidden_layers": 2, "hidden_size": 8, "vocab_size": 16,
           "fedfa": cfg["fedfa"], "reduced": []}
    (copy / "configs" / "toy.json").write_text(json.dumps(cfg))
    cfg = spec.config("toy")

    assert harness.program_config(cfg).d_model == 8
    leaves, n = weights.layout(cfg)
    assert [(l.name, l.shape, l.stacked) for l in leaves] == [
        ("embed", (16, 8), False), ("stages/0/0/mix/w", (2, 8, 8), True)]
    assert n == 16 * 8 + 2 * 8 * 8
    assert reference.sub_sizes(cfg, 0.5) == {"embed": (8, 4),
                                             "mix/w": (4, 4)}
    assert reference.loss({}, jnp.ones((2, 3)), cfg,
                          {"a": (1,), "b": (2,)}) == 2006.0
    assert work.train_flops(cfg, 0.5, (1, 1), 4, 8) == 7.0 * 0.5 * 2 * 32
    assert _unchanged(copy, before)


def test_dense_family_is_what_it_was():
    # numbers the harness gave before the dense family moved into its own
    # file: N and the layer tensors (30 layers x 9 stacked leaves + the
    # embedding and the final norm) of smollm-135m, and the FLOPs of one
    # local step of each width.m3 class (full depth, 8 x 64 tokens)
    import weights
    import work
    cfg = spec.config("smollm-135m")
    leaves, n = weights.layout(cfg)
    assert n == 134_515_008
    assert len(leaves) == 11
    assert sum(l.shape[0] if l.stacked else 1 for l in leaves) == 272
    t = spec.traffic("width.m3")
    want = {0.25: 44_958_744_576.0, 0.5: 134_083_510_272.0,
            1.0: 416_519_553_024.0}
    for cls in t["population"]:
        depths = work.section_depths(cfg, cls["depth"])
        assert work.train_flops(cfg, cls["width"], depths, t["batch"],
                                t["seq_len"]) == want[cls["width"]]


def test_configuration_without_family_is_dense():
    cfg = spec.config("smollm-135m")
    assert "family" not in cfg
    assert spec.family_of(cfg) is spec.family("dense")
    with pytest.raises(FileNotFoundError):
        spec.family("no-such-family")
