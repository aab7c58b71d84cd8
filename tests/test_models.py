"""Model zoo behaviour: parallel-vs-decode equivalence, grads, invariances."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # model-zoo / driver integration tier

from repro.models import lm, ssm, xlstm
from repro.models.config import ArchConfig, MoEConfig
from repro.distributed.sharding import make_mesh

B, T = 2, 16


def _cfg(**kw):
    base = dict(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                n_kv_heads=2, d_ff=128, vocab=128, dtype="float32")
    base.update(kw)
    return ArchConfig(**base)


CFGS = {
    "dense": _cfg(qk_norm=True),
    "parallel": _cfg(parallel_block=True),
    "partial_rope": _cfg(rope_fraction=0.25),
    "moe": _cfg(family="moe", d_ff=0,
                moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=32,
                              n_shared_experts=1)),
    "hybrid": _cfg(family="hybrid", n_layers=4,
                   block_pattern=("mamba", "attn"), ssm_d_state=8,
                   ssm_head_dim=16, ssm_chunk=8),
    "xlstm": _cfg(family="ssm", n_layers=4, n_kv_heads=4,
                  block_pattern=("mlstm", "mlstm", "mlstm", "slstm"),
                  ssm_chunk=8),
}


def _batch(cfg):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)
    return {"tokens": tokens, "labels": tokens}


@pytest.mark.parametrize("name", list(CFGS))
def test_loss_and_grads_finite(name):
    cfg = CFGS[name]
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: lm.loss_fn(p, cfg, _batch(cfg)), has_aux=True)(params)
    assert jnp.isfinite(loss)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    assert 0.0 <= float(metrics["accuracy"]) <= 1.0


@pytest.mark.parametrize("name", list(CFGS))
def test_decode_matches_parallel(name):
    cfg = CFGS[name]
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = _batch(cfg)["tokens"]
    logits_par = lm.forward(params, cfg, tokens, remat=False)
    caches = lm.init_caches(params, cfg, B, T)
    outs = []
    for t in range(T):
        pos = jnp.full((B, 1), t, jnp.int32)
        lg, caches = lm.decode_step(params, cfg, caches, tokens[:, t:t+1],
                                    pos)
        outs.append(lg)
    err = float(jnp.abs(logits_par - jnp.concatenate(outs, 1)).max())
    assert err < 2e-2, err


def test_causality():
    """Perturbing a future token must not change past logits."""
    cfg = CFGS["dense"]
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = _batch(cfg)["tokens"]
    l1 = lm.forward(params, cfg, tokens, remat=False)
    tokens2 = tokens.at[:, -1].set((tokens[:, -1] + 1) % cfg.vocab)
    l2 = lm.forward(params, cfg, tokens2, remat=False)
    assert float(jnp.abs(l1[:, :-1] - l2[:, :-1]).max()) < 1e-5


@pytest.mark.parametrize("block", ["mamba", "mlstm", "slstm"])
def test_recurrent_blocks_match_decode(block):
    cfg = _cfg(n_kv_heads=4, ssm_d_state=8, ssm_head_dim=16, ssm_chunk=8)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, T, cfg.d_model),
                          jnp.float32) * 0.5
    mod = {"mamba": ssm, "mlstm": xlstm, "slstm": xlstm}[block]
    p = getattr(mod, f"{block}_init")(jax.random.PRNGKey(3), cfg)
    y_par = getattr(mod, f"{block}_apply")(p, cfg, x)
    cache = getattr(mod, f"{block}_cache_init")(cfg, B)
    ys = []
    for t in range(T):
        y_t, cache = getattr(mod, f"{block}_decode")(p, cfg, x[:, t:t+1],
                                                     cache)
        ys.append(y_t)
    err = float(jnp.abs(y_par - jnp.concatenate(ys, 1)).max())
    assert err < 1e-4, err


def test_mlstm_chunk_invariance():
    """Chunked mLSTM must be invariant to the chunk size."""
    cfg8 = _cfg(n_kv_heads=4, ssm_chunk=8)
    cfg4 = _cfg(n_kv_heads=4, ssm_chunk=4)
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, cfg8.d_model),
                          jnp.float32)
    p = xlstm.mlstm_init(jax.random.PRNGKey(5), cfg8)
    y8 = xlstm.mlstm_apply(p, cfg8, x)
    y4 = xlstm.mlstm_apply(p, cfg4, x)
    assert float(jnp.abs(y8 - y4).max()) < 1e-4


def test_flash_equals_naive_attention():
    import dataclasses
    cfg = CFGS["dense"]
    cfgf = dataclasses.replace(cfg, attn_impl="flash", flash_block=8)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = _batch(cfg)["tokens"]
    l1 = lm.forward(params, cfg, tokens, remat=False)
    l2 = lm.forward(params, cfgf, tokens, remat=False)
    assert float(jnp.abs(l1 - l2).max()) < 1e-4
    g1 = jax.grad(lambda p: lm.loss_fn(p, cfg, _batch(cfg))[0])(params)
    g2 = jax.grad(lambda p: lm.loss_fn(p, cfgf, _batch(cfg))[0])(params)
    err = max(float(jnp.abs(a - b).max())
              for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
    assert err < 1e-4, err


def test_moe_ep_equals_einsum_on_host_mesh():
    import dataclasses
    from repro.distributed import sharding as shd
    cfg = CFGS["moe"]
    # high capacity factor so no tokens drop (drop order differs per impl)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    cfg_ep = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, impl="ep"))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = _batch(cfg)["tokens"]
    mesh = make_mesh((1, 1), ("data", "model"))
    with shd.use_mesh(mesh):
        l1 = lm.forward(params, cfg, tokens, remat=False)
        l2 = lm.forward(params, cfg_ep, tokens, remat=False)
    assert float(jnp.abs(l1 - l2).max()) < 1e-4


def test_moe_einsum_equals_scatter():
    moe_e = CFGS["moe"]
    moe_s = _cfg(family="moe", d_ff=0,
                 moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=32,
                               n_shared_experts=1, impl="scatter"))
    params = lm.init_params(jax.random.PRNGKey(0), moe_e)
    tokens = _batch(moe_e)["tokens"]
    l1 = lm.forward(params, moe_e, tokens, remat=False)
    l2 = lm.forward(params, moe_s, tokens, remat=False)
    assert float(jnp.abs(l1 - l2).max()) < 1e-3


def test_param_count_matches_init():
    from repro.models.config import param_count
    for name, cfg in CFGS.items():
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        expect = param_count(cfg)
        assert abs(actual - expect) / expect < 0.12, (name, actual, expect)


def test_frontend_embeddings_path():
    cfg = _cfg(family="vlm", frontend="embeddings", frontend_len=4)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    emb = jax.random.normal(jax.random.PRNGKey(6), (B, 4, cfg.d_model))
    batch["embeddings"] = emb
    batch["labels"] = batch["labels"].at[:, :4].set(-1)
    loss, m = lm.loss_fn(params, cfg, batch)
    assert jnp.isfinite(loss)
    # changing the frontend embeddings must change the loss
    batch2 = dict(batch, embeddings=emb + 1.0)
    loss2, _ = lm.loss_fn(params, cfg, batch2)
    assert abs(float(loss - loss2)) > 1e-6


def test_moe_decode_reproduces_capacity_drops():
    """Capacity drops are per-row causal: a decode loop with the count
    cache must reproduce moe_apply token-for-token even when the capacity
    binds (low capacity_factor forces drops)."""
    import dataclasses
    from repro.models import moe as moe_mod

    cfg = CFGS["moe"]
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.5))
    cfg_nodrop = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    p = jax.tree.map(lambda v: v[0],
                     lm.init_params(jax.random.PRNGKey(0),
                                    cfg)["slots"]["slot0"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, cfg.d_model),
                          jnp.float32)
    y_par = moe_mod.moe_apply(p, cfg, x)
    # the tight capacity really drops tokens (outputs differ vs no-drop)
    y_nodrop = moe_mod.moe_apply(p, cfg_nodrop, x)
    assert float(jnp.abs(y_par - y_nodrop).max()) > 1e-3

    cache = moe_mod.moe_cache_init(cfg, B, T)
    outs = []
    for t in range(T):
        y_t, cache = moe_mod.moe_decode(p, cfg, x[:, t:t + 1], cache)
        outs.append(y_t)
    y_dec = jnp.concatenate(outs, axis=1)
    err = float(jnp.abs(y_par - y_dec).max())
    assert err < 1e-4, err
    # einsum and scatter agree on the keep set under forced drops
    cfg_sc = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, impl="scatter"))
    y_sc = moe_mod.moe_apply(p, cfg_sc, x)
    assert float(jnp.abs(y_par - y_sc).max()) < 1e-4
