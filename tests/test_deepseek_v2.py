"""DeepSeek-V2-Lite on the serving path, at SMOKE widths, against the plain
reference of the benchmark (``chipbench/references/deepseek_v2.py``): the
engine's prefill and decode under each serving backend, lane independence
of the dropless routed experts, the grouped format GEMM against its
mirror, and where each product's format comes from."""
import functools
import importlib.util
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.backend import JOps
from repro.kernels import quant_matmul as qm
from repro.launch import batching, serve
from repro.models import layers as L
from repro.models import moe as M
from repro.models import transformer as T

REF_PATH = (pathlib.Path(__file__).resolve().parents[1] / "chipbench"
            / "references" / "deepseek_v2.py")
SMOKE = configs.get("deepseek_v2_lite").SMOKE
# the published config's keys at SMOKE's widths
HF = {"hidden_size": 64, "intermediate_size": 128, "kv_lora_rank": 16,
      "moe_intermediate_size": 32, "n_routed_experts": 8,
      "n_shared_experts": 2, "num_attention_heads": 4,
      "num_key_value_heads": 4, "num_experts_per_tok": 3,
      "num_hidden_layers": 3, "first_k_dense_replace": 1,
      "q_lora_rank": None, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
      "v_head_dim": 8, "vocab_size": 128, "norm_topk_prob": False,
      "routed_scaling_factor": 1, "rms_norm_eps": 1e-6,
      "rope_theta": 10000, "tie_word_embeddings": False,
      "rope_scaling": {"type": "yarn", "factor": 40,
                       "original_max_position_embeddings": 64,
                       "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                       "mscale_all_dim": 0.707}}
LANES, MAX_SEQ, PAGE = 3, 32, 8


def _format_map(k_default, k_attn, k_layer0):
    f = lambda k: {"k": k, "emax": 15, "emin": -24}
    return {"": f(k_default), "layer*/attn": f(k_attn), "layer0": f(k_layer0)}


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("deepseek_v2_ref", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def weights(ref):
    return jax.jit(functools.partial(ref.init_weights, HF))(
        jnp.uint32(7), jnp.uint32(11))


def test_reference_arch_is_the_registered_smoke_config(ref):
    arch = ref.program_arch(HF, SMOKE.name)
    for k, v in arch.items():
        assert getattr(SMOKE, k) == v, k
    full = configs.get("deepseek_v2_lite").FULL
    assert (full.n_layers, full.d_model, full.vocab, full.n_experts,
            full.top_k, full.moe_d_ff, full.d_ff) == (
        27, 2048, 102400, 64, 6, 1408, 10944)
    assert full.q_rank is None and full.first_k_dense == 1
    assert full.n_shared_experts == 2 and not full.norm_topk_prob


def test_yarn_tables_and_softmax_scale_match_the_reference(ref):
    pos = jnp.arange(200)
    cos, sin = L.rope_tables(pos, SMOKE.d_rope, SMOKE.rope_theta, SMOKE.yarn)
    rcos, rsin = ref._yarn_tables(pos, HF)
    np.testing.assert_array_equal(np.asarray(cos), np.asarray(rcos))
    np.testing.assert_array_equal(np.asarray(sin), np.asarray(rsin))
    # the blend is exercised: some frequencies interpolated, some not
    inv = np.asarray(L.yarn_inv_freq(8, 10000.0, 40.0, 64, 32.0, 1.0))
    base = 1.0 / 10000.0 ** (np.arange(0, 8, 2) / 8)
    assert inv[0] == pytest.approx(base[0])
    assert inv[-1] == pytest.approx(base[-1] / 40)
    full = configs.get("deepseek_v2_lite").FULL
    mscale = 0.1 * 0.707 * np.log(40) + 1
    assert mscale == pytest.approx(1.26080, abs=1e-5)
    assert T.softmax_scale(full) == pytest.approx(192 ** -0.5 * mscale ** 2)


def _engine_logits(sc, weights, prompts, force_kernel=None):
    eng = batching.ContinuousBatchingEngine(
        SMOKE, sc, weights, n_lanes=LANES, max_seq=MAX_SEQ, page_size=PAGE,
        queue_depth=8, keep_logits=True)
    if force_kernel is not None:
        eng.bk.force_kernel = force_kernel
        eng._build_steps()
    for i, p in enumerate(prompts):
        eng.submit(batching.Request(rid=i, prompt=p, max_new_tokens=4))
    return {r["id"]: r for r in eng.run()}


@functools.lru_cache(maxsize=None)
def _reference_logits(ref):
    return jax.jit(lambda w, s: ref.logits(w, HF, ref.hidden(w, HF, s)))


def _worst_dev(ref, weights, prompts, out):
    """Widest |served − reference| logit over every served row, as a share
    of the largest |reference logit| (the benchmark's logit_dev)."""
    fn = _reference_logits(ref)
    dev = scale = 0.0
    for i, p in enumerate(prompts):
        r = out[i]
        seq = np.zeros((MAX_SEQ,), np.int32)     # one shape: causal, so
        full = list(p) + r["tokens"][:-1]        # the tail is not seen
        seq[:len(full)] = full
        want = np.asarray(fn(weights, seq))[len(p) - 1:len(full)]
        dev = max(dev, float(np.abs(r["logits"] - want).max()))
        scale = max(scale, float(np.abs(want).max()))
    return dev / scale


# one page bucket, so one prefill program; four requests over three lanes
PROMPTS = [[5, 17, 99, 3, 64, 2, 8], [120, 1, 1, 7], [40, 41, 42, 43, 44],
           [9, 8, 7, 6, 5, 4, 3, 2]]


@pytest.mark.parametrize("backend, tol", [
    # float32 throughout: only summation order differs from the reference
    ("f32", 2e-5),
    # a 20-bit significand map, mirror arm: ~2^-20 per rounding, so a few
    # 1e-6; experts or attention in bfloat16 (8 bits) would read ~1e-2
    ("format", 1e-4),
    # the same map through the Pallas kernels in interpret mode
    ("format-kernels", 1e-4)])
def test_engine_prefill_and_decode_match_the_reference(
        ref, weights, monkeypatch, backend, tol):
    if backend == "f32":
        sc = serve.ServeConfig()
    else:
        sc = serve.ServeConfig(precision_layer_format=_format_map(20, 19, 21))
    force = None
    if backend == "format-kernels":
        force = True
        for name in ("quant_matmul_format_dispatch",
                     "grouped_quant_matmul_format_dispatch"):
            monkeypatch.setattr(qm, name, functools.partial(
                getattr(qm, name), interpret=True))
    out = _engine_logits(sc, weights, PROMPTS, force)
    assert sorted(out) == list(range(len(PROMPTS)))
    assert _worst_dev(ref, weights, PROMPTS, out) < tol


@pytest.mark.parametrize("backend", ["f32", "format"])
def test_a_lane_does_not_see_the_other_lanes(weights, backend):
    """Dropless experts: lane 0's logits are bitwise the same whatever the
    other lanes hold, though their tokens reroute every expert tile."""
    sc = (serve.ServeConfig() if backend == "f32" else
          serve.ServeConfig(precision_layer_format=_format_map(12, 11, 13)))
    bk = batching.make_backend(sc)
    step = jax.jit(lambda w, c, t, o: T.forward(bk, w, SMOKE, t, cache=c,
                                                q_offset=o)[0][:, -1])
    rng = np.random.RandomState(3)
    B = 6
    shapes = T.init_cache(SMOKE, B, MAX_SEQ, jnp.float32, per_lane_idx=True)
    noise = lambda: {n: jnp.asarray(rng.randn(*a.shape), a.dtype)
                     for n, a in shapes.items() if n != "idx"}
    mine = noise()
    rows = []
    for _ in range(3):
        # other lanes: new histories, tokens and positions; lane 0 its own
        cache = {n: a.at[:, 0].set(mine[n][:, 0]) for n, a in noise().items()}
        tokens = jnp.asarray(rng.randint(0, SMOKE.vocab, (B, 1)),
                             jnp.int32).at[0, 0].set(17)
        offs = jnp.asarray([9, *rng.randint(1, 30, B - 1)], jnp.int32)
        cache["idx"] = jnp.broadcast_to(offs, shapes["idx"].shape)
        rows.append(np.asarray(step(weights, cache, tokens, offs)[0]))
    np.testing.assert_array_equal(rows[0], rows[1])
    np.testing.assert_array_equal(rows[0], rows[2])


def _stack(rng, L_, E, K, N):
    return jnp.asarray(rng.randn(L_, E, K, N).astype(np.float32))


@pytest.mark.parametrize("case", ["random", "one_expert", "empty_experts"])
def test_grouped_kernel_is_bitwise_its_mirror(case):
    """Interpret-mode grouped format GEMM == its eager mirror, bit for bit,
    over every row (padding tiles zero in both), with a contraction that
    ends in a short K block. Real rows also equal the dense mirror of each
    row's own expert, to its rounding."""
    rng = np.random.RandomState(0)
    E, T_, k, K, N = 6, 10, 2, 600, 40
    idx = {"random": rng.randint(0, E, (T_, k)),
           "one_expert": np.full((T_, k), 4),
           "empty_experts": rng.choice([1, 5], (T_, k))}[case]
    if case != "one_expert":            # a token's choices differ
        idx[:, 1] = np.where(idx[:, 1] == idx[:, 0], (idx[:, 0] + 1) % E,
                             idx[:, 1])
    r = M.route(jnp.asarray(idx, jnp.int32), E)
    R = r.token.shape[0]
    assert int(r.n_real) * r.tm < R           # padding tiles are there
    w = _stack(rng, 2, E, K, N)
    x = jnp.asarray(rng.randn(T_, K).astype(np.float32))
    rows = jnp.where((r.token >= 0)[:, None],
                     x[jnp.maximum(r.token, 0)], 0.0)
    fmt = jnp.asarray([10, 15, -24], jnp.int32)
    meta = jnp.asarray([1, int(r.n_real)], jnp.int32)
    want = qm.grouped_quant_matmul_format_dispatch(
        rows, w, r.tile_expert, meta, fmt, tm=r.tm, force_kernel=False)
    got = qm.grouped_quant_matmul_format_dispatch(
        rows, w, r.tile_expert, meta, fmt, tm=r.tm, force_kernel=True,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(want[int(r.n_real) * r.tm:]).any()
    each = jax.jit(jax.vmap(lambda xr, e: qm.quant_matmul_format_ref(
        xr[None], w[1, e], fmt)[0]))(jnp.repeat(x, k, axis=0),
                                     jnp.asarray(idx.ravel()))
    np.testing.assert_allclose(np.asarray(want[r.pos.ravel()]),
                               np.asarray(each), rtol=2e-3, atol=1e-3)


def test_route_tiles_hold_one_expert_each():
    rng = np.random.RandomState(1)
    E, tm = 8, 8
    idx = jnp.asarray(rng.randint(0, E, (32, 3)), jnp.int32)
    r = M.route(idx, E, tm)
    tok, te = np.asarray(r.token), np.asarray(r.tile_expert)
    pos = np.asarray(r.pos)
    for t in range(32):
        for c in range(3):
            assert tok[pos[t, c]] == t
            assert te[pos[t, c] // tm] == idx[t, c]
    assert sorted(pos.ravel().tolist()) == sorted(np.flatnonzero(tok >= 0))
    counts = np.bincount(np.asarray(idx).ravel(), minlength=E)
    assert int(r.n_real) == int(np.sum(-(-counts // tm)))
    assert int(np.asarray(r.group_sizes).sum()) == len(tok)


class _Recording(serve.FormatQuantJOps):
    """Records the significand width each product resolves, by scope."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.seen = []

    def _current_fmt(self):
        fmt = super()._current_fmt()
        path = "/".join(self.scope_path)
        if self._dyn is not None:       # the scanned stack: its [L] lane
            outer, n, first = self._stack_ctx
            suffix = self.scope_path[len(outer) + 1:]
            ks = [self._lane_static(outer + [f"layer{first + i}", *suffix])[0]
                  for i in range(n)]
            self.seen.append((path, first, ks))
        else:
            self.seen.append((path, None, [int(fmt[0])]))
        return fmt


def test_each_product_resolves_its_scope_format(weights):
    """``layer0`` (the dense layer) k 13 outside attention, ``layer*/attn``
    k 11, the routed and shared experts and the router k 12: the lanes
    count the dense layer, so the MoE layers are layer1 and layer2."""
    bk = _Recording(_format_map(12, 11, 13))
    toks = jnp.asarray([[3, 4, 5, 6]], jnp.int32)
    jax.eval_shape(lambda w: T.forward(bk, w, SMOKE, toks)[0], weights)
    seen = {(p, f): ks for p, f, ks in bk.seen}
    assert seen[("layer*/attn", 0)] == [11]
    assert seen[("layer*/mlp", 0)] == [13]
    assert seen[("layer*/attn", 1)] == [11, 11]
    for sub in ("router", "experts", "shared"):
        assert seen[(f"layer*/mlp/{sub}", 1)] == [12, 12], sub
    assert set(seen) == {("layer*/attn", 0), ("layer*/mlp", 0),
                         ("layer*/attn", 1), ("layer*/mlp/router", 1),
                         ("layer*/mlp/experts", 1), ("layer*/mlp/shared", 1)}


def test_routed_experts_read_the_whole_stack_in_place(weights):
    """The grouped products get the stacked [L, E, K, N] expert weights
    whole and the layer's index in them, not a per-layer slice."""
    shapes = []

    class Spy(JOps):
        def grouped_matmul(self, x, w, layer, routes):
            shapes.append(tuple(w.shape))
            return super().grouped_matmul(x, w, layer, routes)

    T.forward(Spy(), weights, SMOKE, jnp.asarray([[1, 2, 3]], jnp.int32))
    E, d, f = SMOKE.n_experts, SMOKE.d_model, SMOKE.moe_d_ff
    assert shapes == [(2, E, d, f), (2, E, d, f), (2, E, f, d)]


def test_grouped_format_gemm_refuses_a_multi_device_mesh():
    """No layer shares the routed experts out over a mesh yet: run on each
    device, the grouped product would read every expert there, so the
    format backend refuses rather than replicate them."""
    mesh = types.SimpleNamespace(devices=np.empty((2,)),
                                 axis_names=("data",))
    bk = serve.FormatQuantJOps(_format_map(12, 11, 13), mesh=mesh)
    r = M.route(jnp.asarray([[0, 1], [1, 2]], jnp.int32), 4)
    x = jnp.ones((r.token.shape[0], 8), jnp.float32)
    w = jnp.ones((1, 4, 8, 8), jnp.float32)
    with pytest.raises(NotImplementedError, match="multi-device mesh"):
        bk.grouped_matmul(x, w, 0, r)


@pytest.mark.parametrize("backend", ["k", "layer_k"])
def test_quantised_backends_run_the_experts_dropless_in_their_arithmetic(
        weights, backend):
    """The uniform-k and per-layer-k serving backends take the routed
    experts tile by tile through their own rounded matmul: at 22 bits
    their logits sit within 2^-22-scale rounding of float32's."""
    toks = jnp.asarray([[3, 4, 5, 6, 7, 8]], jnp.int32)
    bk = (serve.QuantJOps(22) if backend == "k" else
          serve.MixedQuantJOps({"layer0": 23, "layer*/mlp": 22}, 22))
    got = T.forward(bk, weights, SMOKE, toks)[0]
    want = T.forward(JOps(), weights, SMOKE, toks)[0]
    scale = float(jnp.abs(want).max())
    assert 0 < float(jnp.abs(got - want).max()) / scale < 1e-4
