"""The check that decides ``correct``, driven at a size a CPU holds
(the chip's look for a TPU skipped, the program's jnp path): a sound run
passes; the control (the reference in bfloat16 in the program's place)
and each fault a training cell can have, planted in the timed path, are
refused."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import compare
import harness
import reference

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


CFG, TRAFFIC = _load("tiny-llama.json"), _load("tiny-both.json")
LIMITS = compare.limits("smollm135m.width.m3")
SEED = 2**31 + 977


def _drive(round_fn=None):
    """Set-up, a short window and the check, as a run makes them."""
    cell = harness.setup(CFG, TRAFFIC, SEED, 1, round_fn=round_fn)
    harness.window(cell, 0.3, round_fn=round_fn)
    harness.free_program(cell)
    nums, _ = harness.check(cell)
    return nums


def test_sound_run_is_correct():
    nums = _drive()
    assert compare.verdict(nums, LIMITS), nums


def test_control_is_refused():
    cell = harness.setup(CFG, TRAFFIC, SEED, 1)
    harness.free_program(cell)
    params0 = harness.weights_mod.make_params(CFG, SEED)
    ctl_l, ctl_s = reference.run(CFG, TRAFFIC, cell.rounds, params0,
                                 dtype=jnp.bfloat16)
    cell.prog_losses, cell.prog_snaps = ctl_l, ctl_s
    nums, _ = harness.check(cell)
    assert not compare.verdict(nums, LIMITS), nums


def _unchanged(driver, g, specs, batches, key):
    _, loss = driver.round(g.copy(), specs, batches, key)
    return g, loss


def _half_batch(driver, g, specs, batches, key):
    tok = batches["tokens"]
    return driver.round(g, specs, {"tokens": tok[:, :, :tok.shape[2] // 2]},
                        key)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_fault_is_refused(fault):
    nums = _drive(fault)
    assert not compare.verdict(nums, LIMITS), nums
