"""The round's own names for its work (``repro.core.obs``): the device
phases as named scopes in the round programs' op metadata, and the
driver's host spans in a profiler trace."""
import glob

import jax
import pytest

from conftest import fl_round_fixture, make_cohort

from repro.core import flat
from repro.core import round as round_mod
from repro.core.server import FLConfig, default_class_masks, stack_runtimes

CFG, PARAMS = fl_round_fixture()
M = 3
KEY = jax.random.PRNGKey(0)

AGG_SCOPES = {"fedfa.unflatten", "fedfa.train", "fedfa.flatten",
              "fedfa.graft", "fedfa.density", "fedfa.quantile",
              "fedfa.accumulate", "fedfa.merge"}
DRIVER_SPANS = ("fedfa.round", "fedfa.runtimes", "fedfa.prepare",
                "fedfa.program")


@pytest.fixture(scope="module")
def cohort():
    return make_cohort(CFG, M, local_steps=2)


def _fl(update_dtype):
    return FLConfig(local_steps=2, lr=0.05, strategy="fedfa", task="cls",
                    agg_engine="flat", update_dtype=update_dtype)


@pytest.mark.parametrize("update_dtype,scopes", [
    ("f32", AGG_SCOPES),
    ("int8", AGG_SCOPES | {"fedfa.quantize"}),
])
def test_round_phases_in_op_metadata(cohort, update_dtype, scopes):
    """Every phase the round runs carries its scope in the lowered
    program's op locations, which XLA keeps as each op's op_name metadata
    and the device trace reports."""
    specs, data_fn = cohort
    fl = _fl(update_dtype)
    index = flat.get_index(PARAMS)
    masks, gates, gmaps, nd, cms, mal = stack_runtimes(CFG, specs)
    state = (round_mod.fresh_quant_state(index, M, update_dtype)
             if update_dtype != "f32"
             else (jax.numpy.zeros((M, index.n_padded)),))
    fn = round_mod.make_flat_round(CFG, fl, index, any_malicious=False)
    text = fn.lower(flat.flatten(index, PARAMS), *state, masks, gates,
                    gmaps, nd, default_class_masks(cms, CFG, fl, M), mal,
                    data_fn(0)[1], jax.random.split(KEY, M)
                    ).as_text(debug_info=True)
    for scope in sorted(scopes):
        assert f"/{scope}/" in text, scope


def test_driver_spans_once_per_round(cohort, tmp_path):
    """fedfa.round and the three spans inside it open and close once per
    ResidentDriver.round, each nested in that round's span."""
    specs, data_fn = cohort
    fl = _fl("f32")
    index = flat.get_index(PARAMS)
    driver = round_mod.ResidentDriver(CFG, fl, index)
    g_buf = flat.flatten(index, PARAMS)
    rounds = 2
    jax.profiler.start_trace(str(tmp_path))
    try:
        for r in range(rounds):
            g_buf, loss = driver.round(g_buf, specs, data_fn(r)[1],
                                       jax.random.fold_in(KEY, r))
        jax.block_until_ready((g_buf, loss))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = {n: [] for n in DRIVER_SPANS}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in spans:
                    spans[ev.name].append((ev.start_ns, ev.end_ns))
    assert all(len(v) == rounds for v in spans.values()), spans
    for name in DRIVER_SPANS[1:]:
        for (s, e), (rs, re_) in zip(sorted(spans[name]),
                                     sorted(spans["fedfa.round"])):
            assert rs <= s <= e <= re_, name
