"""Per-assigned-architecture smoke tests (deliverable f): a REDUCED
config of the same family runs one forward/train step on CPU with
correct output shapes and no NaNs.  Full configs are exercised only
via the dry-run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, REGISTRY, get_arch
from repro.data import lm_batch, mind_batch, molecule_batch

LM_ARCHS = [a for a in ASSIGNED if REGISTRY[a].FAMILY == "lm"]
GNN_ARCHS = [a for a in ASSIGNED if REGISTRY[a].FAMILY == "gnn"]


def finite_tree(t):
    return all(bool(jnp.all(jnp.isfinite(x)))
               for x in jax.tree_util.tree_leaves(t)
               if jnp.issubdtype(x.dtype, jnp.floating))


def test_registry_complete():
    assert len(ASSIGNED) == 10
    assert "sssp" in REGISTRY
    for a in ASSIGNED:
        assert len(REGISTRY[a].SHAPES) == 4, a


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_lm_arch_smoke(arch, key, topo1):
    from repro.models.lm import (
        decode_step, init_params, lm_loss, prefill_step,
    )

    cfg = get_arch(arch).make_config(reduced=True)
    p = init_params(key, cfg)
    batch = {k: jnp.asarray(v) for k, v in
             lm_batch(0, 4, 16, cfg.vocab).items()}
    loss, g = jax.value_and_grad(
        lambda pp: lm_loss(pp, batch, cfg, topo1)
    )(p)
    assert np.isfinite(float(loss)) and 1 < float(loss) < 10, arch
    assert finite_tree(g)
    # serve path: prefill + one decode step
    cache, logits = prefill_step(p, batch["tokens"], cfg, topo1, 32)
    assert logits.shape == (4, cfg.vocab)
    lg, cache2 = decode_step(
        p, cache, batch["tokens"][:, -1], 16, cfg, topo1
    )
    assert lg.shape == (4, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(lg, np.float32)))
    # full-config parameter accounting sanity (the assignment's sizes)
    full = get_arch(arch).make_config(reduced=False)
    declared = {
        "phi3.5-moe-42b-a6.6b": 41.9e9, "dbrx-132b": 131.6e9,
        "phi3-mini-3.8b": 3.8e9, "minitron-8b": 7.7e9,
        "minicpm3-4b": 4.1e9,
    }[arch]
    assert abs(full.n_params() - declared) / declared < 0.03, (
        arch, full.n_params()
    )


@pytest.mark.parametrize("arch", GNN_ARCHS)
def test_gnn_arch_smoke_molecule(arch, key):
    mod = get_arch(arch)
    cfg = mod.make_config(reduced=True, cell="molecule")
    batch = {k: jnp.asarray(v) for k, v in molecule_batch(
        0, 4, 10, 20, triplets=True, triplet_pad=128).items()}
    from repro.models.gnn import dimenet, egnn, gin, mace

    impl = {"mace": mace, "egnn": egnn, "dimenet": dimenet,
            "gin-tu": gin}[arch]
    p = impl.init_params(key, cfg)
    if arch == "gin-tu":
        from repro.configs.gin_tu import _molecule_loss

        loss_fn = lambda pp: _molecule_loss(pp, batch, cfg)
    else:
        loss_fn = lambda pp: impl.regression_loss(pp, batch, cfg)
    loss, g = jax.value_and_grad(loss_fn)(p)
    assert np.isfinite(float(loss)), arch
    assert finite_tree(g), arch


@pytest.mark.parametrize("arch", GNN_ARCHS)
def test_gnn_arch_smoke_flat(arch, key):
    """Node-classification on a small real topology."""
    from repro.data import gnn_flat_batch
    from repro.graph import small_world_graph
    from repro.models.gnn import dimenet, egnn, gin, mace

    mod = get_arch(arch)
    cfg = mod.make_config(reduced=True, cell="full_graph_sm")
    g = small_world_graph(120, seed=1)
    need_coords = arch != "gin-tu"
    need_tri = arch == "dimenet"
    batch = {k: jnp.asarray(v) for k, v in gnn_flat_batch(
        g, d_feat=cfg.d_in, n_classes=max(cfg.n_classes, 2),
        coords=need_coords, triplets=need_tri).items()}
    impl = {"mace": mace, "egnn": egnn, "dimenet": dimenet,
            "gin-tu": gin}[arch]
    p = impl.init_params(key, cfg)
    loss = impl.node_classification_loss(p, batch, cfg)
    assert np.isfinite(float(loss)), arch


def test_mind_arch_smoke(key):
    from repro.models import mind as mind_mod

    cfg = get_arch("mind").make_config(reduced=True)
    p = mind_mod.init_params(key, cfg)
    batch = {k: jnp.asarray(v) for k, v in
             mind_batch(0, 8, cfg).items()}
    loss, g = jax.value_and_grad(
        lambda pp: mind_mod.sampled_softmax_loss(pp, batch, cfg)
    )(p)
    assert np.isfinite(float(loss))
    assert abs(float(loss) - np.log(1 + cfg.n_negatives)) < 1.0
    assert finite_tree(g)
    caps = mind_mod.serve_interests(p, batch, cfg)
    assert caps.shape == (8, cfg.n_interests, cfg.embed_dim)
    # squash keeps capsule norms < 1
    assert float(jnp.max(jnp.linalg.norm(caps, axis=-1))) <= 1.0 + 1e-5
    sc = mind_mod.retrieval_scores(
        p, batch, jnp.arange(100, dtype=jnp.int32), cfg
    )
    assert sc.shape == (8, 100)
    # retrieval score == max over interests of dot products
    cand = jnp.take(p["item_table"], jnp.arange(100), axis=0)
    manual = jnp.max(jnp.einsum("bkd,nd->bkn", caps, cand), axis=1)
    np.testing.assert_allclose(np.asarray(sc), np.asarray(manual),
                               rtol=1e-5)


def test_all_cells_constructible_single_device():
    """Every (arch × shape) cell builds: abstract args and sharding
    trees are structurally compatible (full lowering happens in the
    512-device dry-run)."""
    from repro.configs import all_cells
    from repro.launch.mesh import make_local_topology

    topo = make_local_topology(1)
    built = 0
    for arch, cell in all_cells():
        prog = get_arch(arch).make_cell(cell, topo)
        jax.tree_util.tree_map(lambda a, s: None, prog.args,
                               prog.in_shardings)
        built += 1
    assert built == 47  # 10 archs x 4 shapes + 7 sssp cells
