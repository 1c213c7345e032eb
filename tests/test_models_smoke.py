"""Per-architecture smoke tests (deliverable f): reduced config of each
family, one forward/train step on CPU, output shapes + no NaNs; plus
decode-vs-teacher-forced consistency and the ITA quantized path."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, get_config
from repro.models import forward, init_caches, init_model, loss_fn

KEY = jax.random.PRNGKey(0)

# big/exotic stacks and duplicate-family configs dominate suite wall-clock —
# default tier-1 keeps one arch per family (qwen2 dense, mixtral moe+swa,
# phi3 dense, rwkv6 recurrent), the rest run under --runslow (nightly lane)
_HEAVY = {"recurrentgemma-2b", "llama-3.2-vision-90b", "whisper-large-v3",
          "gemma2-27b", "olmoe-1b-7b", "deepseek-coder-33b"}


def _arch_params(archs):
    return [pytest.param(a, marks=pytest.mark.slow) if a in _HEAVY else a
            for a in archs]


@functools.lru_cache(maxsize=None)
def _cfg_params(arch, impl="float"):
    """Share configs + initialized params across the per-arch tests."""
    cfg = get_config(arch, smoke=True, attention_impl=impl)
    return cfg, init_model(KEY, cfg)


def _batch(cfg, b=2, s=16):
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.frontend_dim:
        batch["frontend"] = jax.random.normal(
            KEY, (b, cfg.n_frontend_tokens, cfg.frontend_dim), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", _arch_params(ARCH_IDS))
def test_forward_and_train_step(arch):
    cfg, params = _cfg_params(arch)
    batch = _batch(cfg)
    logits, _, _ = forward(params, batch["tokens"], cfg, mode="train",
                           frontend=batch.get("frontend"))
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, cfg)
    assert bool(jnp.isfinite(loss))
    gsq = jax.tree.reduce(lambda a, b: a + b,
                          jax.tree.map(lambda g: jnp.sum(jnp.square(g)),
                                       grads))
    assert bool(jnp.isfinite(gsq))


@pytest.mark.parametrize("arch", _arch_params(ARCH_IDS))
def test_decode_matches_teacher_forcing(arch):
    cfg, params = _cfg_params(arch)
    b, s = 2, 24
    batch = _batch(cfg, b, s)
    fe = batch.get("frontend")
    full, _, _ = forward(params, batch["tokens"], cfg, mode="train",
                         frontend=fe)
    caches = init_caches(cfg, b, max_len=s + 4)
    lp, caches, _ = forward(params, batch["tokens"][:, :s - 1], cfg,
                            mode="prefill", frontend=fe, caches=caches)
    ld, _, _ = forward(params, batch["tokens"][:, s - 1:s], cfg,
                       mode="decode", frontend=fe, caches=caches, pos0=s - 1)
    np.testing.assert_allclose(np.asarray(ld[:, 0]),
                               np.asarray(full[:, -1]), atol=2e-3)
    np.testing.assert_allclose(np.asarray(lp[:, -1]),
                               np.asarray(full[:, -2]), atol=2e-3)


@pytest.mark.parametrize("arch", [
    "qwen2-7b",
    pytest.param("gemma2-27b", marks=pytest.mark.slow),
    pytest.param("mixtral-8x7b", marks=pytest.mark.slow),
    pytest.param("whisper-large-v3", marks=pytest.mark.slow),
    pytest.param("recurrentgemma-2b", marks=pytest.mark.slow)])
def test_ita_quantized_path(arch):
    """QAT train grads finite + integer serve path finite with int8 cache."""
    cfg, params = _cfg_params(arch, "ita")
    batch = _batch(cfg)
    (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, cfg)
    gsq = jax.tree.reduce(lambda a, b: a + b,
                          jax.tree.map(lambda g: jnp.sum(jnp.square(g)),
                                       grads))
    assert bool(jnp.isfinite(loss)) and bool(jnp.isfinite(gsq))

    caches = init_caches(cfg, 2, max_len=20)
    lp, caches, _ = forward(params, batch["tokens"], cfg, mode="prefill",
                            frontend=batch.get("frontend"), caches=caches)
    ld, _, _ = forward(params, batch["tokens"][:, -1:], cfg, mode="decode",
                       frontend=batch.get("frontend"), caches=caches,
                       pos0=16)
    assert bool(jnp.all(jnp.isfinite(ld)))
    kv_dtypes = {l.dtype for path, l in
                 jax.tree_util.tree_flatten_with_path(caches)[0]
                 if any(getattr(k, "key", getattr(k, "name", None))
                        in ("k", "v", "k8", "v8") for k in path)}
    assert kv_dtypes == {jnp.dtype(jnp.int8)}, kv_dtypes


def test_ita_vs_float_logits_close():
    """End to end: ITA integer serving approximates the float model on a
    QAT-consistent checkpoint (same random params here)."""
    cfg_f = get_config("phi3-mini-3.8b", smoke=True)
    cfg_q = get_config("phi3-mini-3.8b", smoke=True, attention_impl="ita")
    params = init_model(KEY, cfg_f)
    from repro.models.transformer import init_model as im
    params_q = im(KEY, cfg_q)
    # share the float weights
    for k in ("embed", "final_norm"):
        params_q[k] = params[k]
    batch = _batch(cfg_f)
    lf, _, _ = forward(params, batch["tokens"], cfg_f, mode="train")
    caches = init_caches(cfg_q, 2, max_len=25)
    lq, _, _ = forward(params_q, batch["tokens"], cfg_q, mode="prefill",
                       caches=caches)
    # same argmax on most positions (quantization-consistent behaviour)
    agree = (jnp.argmax(lf, -1) == jnp.argmax(lq, -1)).mean()
    assert float(agree) > 0.5, float(agree)


def test_swa_ring_buffer_long_decode():
    """Sliding-window ring cache: decoding past the window keeps only the
    last `window` tokens and matches teacher forcing."""
    cfg, params = _cfg_params("mixtral-8x7b")      # window 16
    b, s = 1, 40                                    # 2.5x window
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    full, _, _ = forward(params, tokens, cfg, mode="train")
    caches = init_caches(cfg, b, max_len=s)
    _, caches, _ = forward(params, tokens[:, :s - 1], cfg, mode="prefill",
                           caches=caches)
    ld, _, _ = forward(params, tokens[:, s - 1:], cfg, mode="decode",
                       caches=caches, pos0=s - 1)
    np.testing.assert_allclose(np.asarray(ld[:, 0]), np.asarray(full[:, -1]),
                               atol=2e-3)


def test_init_serving_params_is_the_cast_init_model():
    """Serving weights are created in the compute dtype under one jit —
    the same values as casting the f32 ``init_model`` tree."""
    from repro.models import init_serving_params
    cfg = get_config("phi3-mini-3.8b", smoke=True, dtype="bfloat16")
    got = init_serving_params(KEY, cfg)
    want = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                        init_model(KEY, cfg))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize("width", [3072, 96, 1280, 7])
def test_row_mean_is_the_mean_summed_in_one_order(width):
    """``_row_mean`` (the norms' fixed-order sum) is the mean to f32
    rounding, and a row's value does not depend on the rows beside it."""
    from repro.models.layers import _row_mean, apply_norm
    x = jax.random.normal(KEY, (9, width), jnp.float32) * 3.0
    np.testing.assert_allclose(np.asarray(_row_mean(x))[:, 0],
                               np.asarray(x).mean(-1), rtol=1e-5, atol=1e-6)
    p = {"scale": jnp.zeros((width,), jnp.float32)}
    xb = x.astype(jnp.bfloat16)
    alone = jax.jit(apply_norm)(p, xb[3:4])
    np.testing.assert_array_equal(np.asarray(alone, np.float32),
                                  np.asarray(jax.jit(apply_norm)(p, xb)[3:4],
                                             np.float32))
