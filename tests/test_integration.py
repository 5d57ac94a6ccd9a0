"""Integration: full FL rounds across strategies, attack robustness trend,
sharded lowering on a host mesh, and a short convergence run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny

from repro.core.server import FLConfig, fl_round, make_client_specs
from repro.models import model as model_mod
from repro.models.masks import ClientArch


def _setup(vocab=64, n_clients=6, mal=0.0, seed=0):
    cfg = tiny("smollm-135m").replace(n_layers=4, n_sections=2,
                                      vocab_size=vocab)
    params = model_mod.init_params(cfg, jax.random.PRNGKey(seed))
    archs = [ClientArch(0.5, (1, 1)), ClientArch(0.75, (2, 1)),
             ClientArch(1.0, (2, 2))]
    specs = make_client_specs(cfg, n_clients, archs=archs,
                              malicious_frac=mal, seed=seed)
    E, B, S = 2, 2, 16
    batches = {"tokens": jax.random.randint(
        jax.random.PRNGKey(seed + 1), (n_clients, E, B, S), 0, vocab)}
    return cfg, params, specs, batches


@pytest.mark.parametrize("strategy", ["fedfa", "heterofl", "flexifed",
                                      "nefl", "fedfa-graft-only",
                                      "fedfa-scale-only"])
def test_round_all_strategies(strategy):
    cfg, params, specs, batches = _setup()
    fl = FLConfig(local_steps=2, lr=0.05, strategy=strategy)
    new_p, loss = fl_round(params, cfg, fl, specs, batches,
                           jax.random.PRNGKey(2))
    assert jnp.isfinite(loss)
    assert not any(bool(jnp.isnan(x).any()) for x in jax.tree.leaves(new_p))
    delta = max(float(jnp.abs(a - b).max())
                for a, b in zip(jax.tree.leaves(new_p), jax.tree.leaves(params)))
    assert delta > 0


@pytest.mark.slow
def test_attack_perturbs_fedfa_less_than_partial():
    """The paper's core claim, miniature: under a strong backdoor (lambda
    large, attacker on the largest arch), FedFA's global model moves less
    from the honest aggregate than incomplete aggregation does."""
    cfg, params, specs, batches = _setup(n_clients=6, mal=0.34, seed=3)
    lam = 20.0

    outs = {}
    for strategy in ["fedfa", "nefl"]:
        fl = FLConfig(local_steps=2, lr=0.05, strategy=strategy,
                      attack_lambda=lam)
        clean_specs = [type(s)(arch=s.arch, n_data=s.n_data, malicious=False,
                               class_mask=s.class_mask) for s in specs]
        p_att, _ = fl_round(params, cfg, fl, specs, batches,
                            jax.random.PRNGKey(4))
        p_cln, _ = fl_round(params, cfg, fl, clean_specs, batches,
                            jax.random.PRNGKey(4), any_malicious=False)
        dev = sum(float(jnp.sum(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(p_att), jax.tree.leaves(p_cln)))
        norm = sum(float(jnp.sum(jnp.abs(b))) for b in jax.tree.leaves(p_cln))
        outs[strategy] = dev / norm
    assert outs["fedfa"] < outs["nefl"], outs


@pytest.mark.parametrize("engine", ["flat", "tree"])
def test_sharded_round_on_host_mesh(engine):
    """The SPMD FL round lowers and runs under a (1,1) mesh with the client
    axis marked for the data axis — the same program the pod runs — with
    either aggregation engine."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    cfg, params, specs, batches = _setup()
    fl = FLConfig(local_steps=2, lr=0.05, strategy="fedfa", agg_engine=engine)
    mesh = make_host_mesh()
    with mesh:
        f = jax.jit(lambda p, b, k: fl_round(p, cfg, fl, specs, b, k),
                    in_shardings=(None,
                                  {"tokens": NamedSharding(mesh, P("data"))},
                                  None))
        new_p, loss = f(params, batches, jax.random.PRNGKey(0))
    assert jnp.isfinite(loss)


@pytest.mark.slow
def test_fl_converges_on_classification():
    from repro.launch.train import run_fl
    hist = run_fl("smollm-135m", rounds=6, n_clients=8, strategy="fedfa",
                  local_steps=2, batch=4, seq_len=32, lr=0.05,
                  participation=0.5, eval_every=5, seed=0,
                  reduced=True)
    assert hist["global_acc"][-1] > hist["global_acc"][0] + 0.1
    assert hist["final_acc"] > 0.35
