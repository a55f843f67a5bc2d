"""Per-architecture smoke tests (assignment requirement): reduced same-family
configs, one forward + one train step on CPU, asserting shapes and no NaNs;
plus a decode step against the cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.backend import JOps, UnrolledLayerLoop
from repro.models import transformer as T


def _batch_kwargs(cfg, B, rng):
    kwargs = {}
    if cfg.frontend == "audio":
        kwargs["enc_embeds"] = rng.randn(B, cfg.frontend_seq,
                                         cfg.frontend_dim).astype(np.float32)
    elif cfg.frontend == "vision":
        kwargs["frontend_embeds"] = rng.randn(B, cfg.frontend_seq,
                                              cfg.frontend_dim).astype(np.float32)
    return kwargs


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_smoke_forward_and_shapes(arch):
    cfg = configs.get(arch).SMOKE
    bk = JOps()
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    B, S = 2, 16
    rng = np.random.RandomState(0)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    logits, _ = T.forward(bk, params, cfg, tokens, **_batch_kwargs(cfg, B, rng))
    exp_s = S + (cfg.frontend_seq if cfg.frontend == "vision" else 0)
    assert logits.shape == (B, exp_s, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_smoke_train_step(arch):
    cfg = configs.get(arch).SMOKE
    bk = JOps()
    key = jax.random.PRNGKey(1)
    params = T.init_params(key, cfg)
    B, S = 2, 16
    rng = np.random.RandomState(1)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    targets = jax.random.randint(key, (B, S), 0, cfg.vocab)
    kwargs = _batch_kwargs(cfg, B, rng)
    loss, grads = jax.value_and_grad(
        lambda p: T.next_token_loss(bk, p, cfg, tokens, targets, **kwargs)
    )(params)
    assert bool(jnp.isfinite(loss))
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grads))


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_smoke_decode_step(arch):
    cfg = configs.get(arch).SMOKE
    bk = JOps()
    key = jax.random.PRNGKey(2)
    params = T.init_params(key, cfg)
    B, Smax = 2, 32
    rng = np.random.RandomState(2)
    kwargs = _batch_kwargs(cfg, B, rng)
    cache = T.init_cache(cfg, B, Smax, jnp.float32)
    tok = jax.random.randint(key, (B, 1), 0, cfg.vocab)
    for pos in range(3):
        logits, cache = T.forward(bk, params, cfg, tok, cache=cache,
                                  q_offset=pos, **kwargs)
        assert bool(jnp.isfinite(logits).all())
        tok = jnp.argmax(logits[:, -1:, :], axis=-1)


def test_decode_matches_full_forward_dense():
    """Step-by-step decode must agree with the full forward (teacher-forced)."""
    cfg = configs.get("qwen2_7b").SMOKE
    bk = JOps()
    key = jax.random.PRNGKey(3)
    params = T.init_params(key, cfg)
    B, S = 1, 8
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    full_logits, _ = T.forward(bk, params, cfg, tokens)
    cache = T.init_cache(cfg, B, S, jnp.float32)
    outs = []
    for i in range(S):
        logits, cache = T.forward(bk, params, cfg, tokens[:, i:i + 1],
                                  cache=cache, q_offset=i)
        outs.append(logits[:, 0])
    step_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(full_logits), rtol=2e-3, atol=2e-3)


def test_decode_matches_full_forward_rwkv():
    cfg = configs.get("rwkv6_1p6b").SMOKE
    bk = JOps()
    key = jax.random.PRNGKey(4)
    params = T.init_params(key, cfg)
    B, S = 1, 8
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    full_logits, _ = T.forward(bk, params, cfg, tokens)
    cache = T.init_cache(cfg, B, S, jnp.float32)
    outs = []
    for i in range(S):
        logits, cache = T.forward(bk, params, cfg, tokens[:, i:i + 1],
                                  cache=cache, q_offset=i)
        outs.append(logits[:, 0])
    step_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(full_logits), rtol=5e-3, atol=5e-3)


class _UnrolledJOps(UnrolledLayerLoop, JOps):
    pass


@pytest.mark.parametrize("arch", ["qwen2_7b", "minicpm3_4b", "rwkv6_1p6b",
                                  "hymba_1p5b", "deepseek_v2_lite"])
def test_scanned_cache_carry_matches_unrolled(arch):
    """The scanned layer loop threads the stacked cache through its carry;
    the unrolled reference runs the same layer function with a static
    layer index. A chunk then a decode step at batch 3, each lane at its
    own position, give bitwise-equal logits and new caches, for each kind
    of cache: GQA K/V, MLA latent, RWKV state, hybrid K/V + SSM state, and
    one latent cache written by a leading dense stack and the MoE stack
    after it (DeepSeek)."""
    cfg = configs.get(arch).SMOKE
    params = T.init_params(jax.random.PRNGKey(6), cfg)
    rng = np.random.RandomState(6)
    B, Smax = 3, 16
    # prior contents everywhere, so a read or write of the wrong layer or
    # lane shows in the logits or the cache
    cache = {n: (a if a.dtype == jnp.int32 else
                 jnp.asarray(0.1 * rng.randn(*a.shape), a.dtype))
             for n, a in T.init_cache(cfg, B, Smax, jnp.float32,
                                      per_lane_idx=True).items()}
    offsets = jnp.asarray([0, 5, 11], jnp.int32)
    if "idx" in cache:
        cache["idx"] = jnp.broadcast_to(offsets, cache["idx"].shape)

    # params are arguments, as in the engine's programs: closed over, they
    # become constants that XLA:CPU folds into other kernels in each form
    def step(bk):
        return jax.jit(lambda p, c, t, o: T.forward(bk, p, cfg, t, cache=c,
                                                    q_offset=o))

    scanned, unrolled = step(JOps()), step(_UnrolledJOps())
    got_c = want_c = cache
    for t, o in ((2, offsets), (1, offsets + 2)):
        tokens = jnp.asarray(rng.randint(0, cfg.vocab, (B, t)), jnp.int32)
        got, got_c = scanned(params, got_c, tokens, o)
        want, want_c = unrolled(params, want_c, tokens, o)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert sorted(got_c) == sorted(cache)
        for n in cache:
            np.testing.assert_array_equal(np.asarray(got_c[n]),
                                          np.asarray(want_c[n]), err_msg=n)
    # the two steps wrote every layer, and each lane's KV rows only at its
    # own three positions (the second-to-last axis), nothing else
    for n in cache:
        changed = np.asarray(got_c[n] != cache[n])
        assert changed.reshape(changed.shape[0], -1).any(axis=1).all(), n
    pos = np.arange(Smax)[None, :]
    lo = np.asarray(offsets)[:, None]
    for n in ("k", "v") if "idx" in cache else ():
        changed = np.asarray(got_c[n] != cache[n]).any(axis=-1)
        changed = changed.any(axis=2) if changed.ndim == 4 else changed
        np.testing.assert_array_equal(
            changed, np.broadcast_to((pos >= lo) & (pos < lo + 3),
                                     changed.shape), err_msg=n)
    if "idx" in cache:
        np.testing.assert_array_equal(
            np.asarray(got_c["idx"]),
            np.broadcast_to(np.asarray(offsets) + 3, cache["idx"].shape))


def test_full_configs_match_assignment():
    """The FULL configs carry the exact assigned hyper-parameters."""
    c = configs.get("mixtral_8x22b").FULL
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads) == (56, 6144, 48, 8)
    assert (c.d_ff, c.vocab, c.n_experts, c.top_k) == (16384, 32768, 8, 2)
    c = configs.get("llama4_maverick").FULL
    assert (c.n_layers, c.d_model, c.vocab, c.n_experts, c.top_k) == (
        48, 5120, 202048, 128, 1)
    c = configs.get("qwen2_7b").FULL
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab, c.qkv_bias) == (28, 3584, 28, 4, 18944, 152064, True)
    c = configs.get("gemma2_27b").FULL
    assert (c.n_layers, c.d_model, c.d_ff, c.vocab) == (46, 4608, 36864, 256000)
    assert c.softcap_attn == 50.0 and c.softcap_final == 30.0
    c = configs.get("command_r_35b").FULL
    assert (c.n_layers, c.d_model, c.n_heads, c.d_ff) == (40, 8192, 64, 22528)
    c = configs.get("minicpm3_4b").FULL
    assert c.mla and (c.n_layers, c.d_model, c.d_ff, c.vocab) == (
        62, 2560, 6400, 73448)
    c = configs.get("rwkv6_1p6b").FULL
    assert c.rwkv and (c.n_layers, c.d_model, c.d_ff, c.vocab) == (
        24, 2048, 7168, 65536)
    c = configs.get("hymba_1p5b").FULL
    assert c.hybrid and (c.n_layers, c.d_model, c.d_ff, c.vocab,
                         c.ssm_state) == (32, 1600, 5504, 32001, 16)
    c = configs.get("whisper_medium").FULL
    assert c.enc_dec and (c.n_layers, c.n_enc_layers, c.d_model,
                          c.d_ff) == (24, 24, 1024, 4096)
    c = configs.get("deepseek_v2_lite").FULL
    assert c.mla and c.q_rank is None and (
        c.n_layers, c.d_model, c.n_heads, c.kv_rank, c.d_nope, c.d_rope,
        c.d_v) == (27, 2048, 16, 512, 128, 64, 128)
    assert (c.d_ff, c.vocab, c.n_experts, c.top_k, c.moe_d_ff,
            c.first_k_dense, c.n_shared_experts) == (10944, 102400, 64, 6,
                                                     1408, 1, 2)
    assert c.yarn == (40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    c = configs.get("paligemma_3b").FULL
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (18, 2048, 8, 1, 16384, 257216)
