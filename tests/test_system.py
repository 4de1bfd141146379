"""End-to-end behaviour: training learns, serving generates, the matching
system solves the paper's workload end-to-end, optimizer semantics hold."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (MatcherConfig, cheap_matching_jax,
                        maximum_cardinality, maximum_matching,
                        validate_matching)
from repro.data import DataConfig, synthetic_batch
from repro.graphs import instance_sets
from repro.models import build_model
from repro.optim import OptConfig, adamw_init, adamw_update
from repro.train import build_train_step, cross_entropy


def test_training_learns_structured_data():
    """~80 steps on the copy-structured stream must cut loss well below
    ln(V)~6.2 (the run reaches ~2.5 by step 60; see examples/train_lm.py)."""
    cfg = get_config("h2o-danube-1.8b", smoke=True)
    model = build_model(cfg)
    params, specs = model.init(jax.random.PRNGKey(0))
    opt_cfg = OptConfig(lr=1e-2, warmup=10, weight_decay=0.0)
    opt, _ = adamw_init(params, specs, opt_cfg)
    step = jax.jit(build_train_step(model, opt_cfg))
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=16)
    first = last = None
    for i in range(80):
        batch = {k: jnp.asarray(v) for k, v in synthetic_batch(dcfg, i).items()}
        params, opt, m = step(params, opt, batch)
        if first is None:
            first = float(m["loss"])
        last = float(m["loss"])
    assert last < first * 0.5, (first, last)


def test_microbatched_train_step_matches():
    cfg = get_config("h2o-danube-1.8b", smoke=True)
    model = build_model(cfg)
    params, specs = model.init(jax.random.PRNGKey(0))
    opt_cfg = OptConfig(lr=1e-3, warmup=1)
    opt, _ = adamw_init(params, specs, opt_cfg)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8)
    batch = {k: jnp.asarray(v) for k, v in synthetic_batch(dcfg, 0).items()}
    s1 = jax.jit(build_train_step(model, opt_cfg))
    s4 = jax.jit(build_train_step(model, opt_cfg, microbatch=4))
    p1, _, m1 = s1(params, opt, batch)
    p4, _, m4 = s4(params, opt, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=1e-4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-3, atol=2e-4)


def test_serve_generates():
    from repro.launch.serve import run
    out = run("mamba2-2.7b", smoke=True, batch=2, prompt_len=8, gen=8)
    assert out.shape == (2, 8)
    assert (out >= 0).all() and (out < 512).all()


def test_matching_end_to_end_instance_suite():
    """The paper's workload: full tiny instance suite, original + RCP."""
    best = MatcherConfig(algo="apfb", kernel="gpubfs_wr")
    for name, g in instance_sets("tiny").items():
        for tag, gg in (("orig", g), ("rcp", g.permuted(13))):
            opt = maximum_cardinality(gg)
            cm0, rm0 = cheap_matching_jax(gg)
            cm, rm, st = maximum_matching(gg, best, cm0, rm0)
            assert validate_matching(gg, cm, rm) == opt, (name, tag, st)


def test_cross_entropy_chunked_matches_plain():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (2, 128, 50))
    labels = jax.random.randint(key, (2, 128), 0, 50)
    a = cross_entropy(logits, labels, chunk=1024)   # plain path
    b = cross_entropy(logits, labels, chunk=32)     # chunked path
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)


def test_adamw_factored_close_to_full():
    """Factored AdamW must track full AdamW directionally on a quadratic."""
    key = jax.random.PRNGKey(0)
    W = jax.random.normal(key, (16, 16))
    params = {"w": jnp.zeros((16, 16))}

    def loss(p):
        return jnp.sum(jnp.square(p["w"] - W))

    outs = {}
    for factored in (False, True):
        cfg = OptConfig(lr=0.05, warmup=1, factored=factored,
                        master_fp32=not factored, weight_decay=0.0)
        p = params
        st, _ = adamw_init(p, {"w": jax.sharding.PartitionSpec()}, cfg)
        for _ in range(60):
            g = jax.grad(loss)(p)
            p, st, _ = adamw_update(p, g, st, cfg)
        outs[factored] = loss(p)
    assert float(outs[True]) < float(loss(params)) * 0.05
    assert float(outs[False]) < float(loss(params)) * 0.05
