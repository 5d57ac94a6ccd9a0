"""Work counts and peaks against values worked out by hand."""
import json
import os

import numpy as np
import pytest

import peaks
import spec
import traffic
import work

# the paper's Table 4 LM has no cell yet: its file is test data here
DIRS = (os.path.join(spec.HERE, "configs"),
        os.path.join(spec.HERE, "tests", "data"))


def _cfg(name):
    path = next(p for p in (os.path.join(d, name + ".json") for d in DIRS)
                if os.path.isfile(p))
    with open(path) as f:
        return json.load(f)


def test_smollm_full_client_step_flops():
    # D 576, H 9, K 3, hd 64, F 1536, 30 layers, V 49152, B 8, S 64:
    # proj 2*512*576*15*64 + 2*512*9*64*576 = 905,969,664
    # attn 4*512*32*9*64 = 37,748,736; ffn 6*512*576*1536 = 2,717,908,992
    # head 2*512*576*49152 = 28,991,029,248; x 3 for forward + backward
    cfg = _cfg("smollm-135m")
    fwd = 30 * (905_969_664 + 37_748_736 + 2_717_908_992) + 28_991_029_248
    got = work.train_flops(cfg, 1.0, work.section_depths(cfg, 1.0), 8, 64)
    assert got == 3 * fwd == 416_519_553_024


def test_paper_tf_thin_client_step_flops():
    # width 0.25: d 48, 1 q head, 1 kv head, d_ff 192; depth 0.5: 2 of 4
    # proj 2*512*48*3*64 + 2*512*64*48 = 12,582,912; attn 4*512*32*64 =
    # 4,194,304; ffn 6*512*48*192 = 28,311,552; head 2*512*48*28800
    cfg = _cfg("fedfa-paper-transformer")
    depths = work.section_depths(cfg, 0.5)
    assert depths == (2,)
    fwd = 2 * (12_582_912 + 4_194_304 + 28_311_552) + 1_415_577_600
    assert work.train_flops(cfg, 0.25, depths, 8, 64) == 3 * fwd


@pytest.mark.parametrize("name,w,want", [
    ("smollm-135m", 0.25, {"d_model": 144, "n_heads": 3, "n_kv_heads": 1,
                           "d_ff": 384}),
    ("smollm-135m", 0.5, {"d_model": 288, "n_heads": 6, "n_kv_heads": 2,
                          "d_ff": 768}),
    ("fedfa-paper-transformer", 0.75, {"d_model": 144, "n_heads": 2,
                                       "n_kv_heads": 2, "d_ff": 576}),
])
def test_width_sizes(name, w, want):
    assert spec.family("dense").width_sizes(_cfg(name), w) == want


def test_least_bytes_and_bound():
    n = 134_515_008
    assert work.quantile_least_bytes(3, n) == 1_614_180_096
    assert work.accum_least_bytes(3, n) == 1_614_180_096 + 8 * n
    p = peaks.peaks_for("TPU v5 lite")
    t, bound = work.least_seconds(work.quantile_least_ops(3, n),
                                  work.quantile_least_bytes(3, n), p)
    assert bound == "hbm"
    assert t == pytest.approx(1_614_180_096 / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("name,n", [("smollm-135m", 134_515_008),
                                    ("fedfa-paper-transformer", 13_420_224)])
def test_flat_length(name, n):
    import weights
    cfg = _cfg(name)
    assert weights.layout(cfg)[1] == n == cfg["fedfa"]["n_flat"]


def test_cohorts_have_fixed_class_mix():
    cfg = _cfg("smollm-135m")
    t = spec.traffic("width.m3")
    assert traffic.cohort_size(t) == 3
    mixes = set()
    for seed in (1, 2**31 + 5):
        for r in traffic.make_rounds(cfg, t, seed):
            mixes.add(tuple(sorted((c.width, c.depths) for c in r.clients)))
            assert r.tokens.shape == (3, 2, 8, 64)
            assert r.tokens.max() < cfg["vocab_size"]
    assert len(mixes) == 1


def test_clients_share_one_unigram_law():
    # IID clients: the most frequent token (Zipf rank 1, ~9% of 1,024
    # tokens a client a round) is the same token id for every client
    cfg = _cfg("smollm-135m")
    for r in traffic.make_rounds(cfg, spec.traffic("width.m3"), 2**31 + 9):
        tops = {int(np.bincount(t.ravel()).argmax()) for t in r.tokens}
        assert len(tops) == 1
