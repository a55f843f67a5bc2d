"""The certified serving kernels compile for a TPU v5e at qwen2_7b and
DeepSeek-V2-Lite widths.

Nothing runs: the TPU compiler, which is installed without a chip,
compiles for a described v5e. It refuses what interpret mode accepts —
blocks off the (8, 128) tiling, more fast memory than a kernel may use,
ops Mosaic cannot lower (64-bit values under the x64 mode ``import repro``
turns on). This file is the only one that describes the chip: the
topology is described inside a fixture, never at import, because only one
process at a time may load the TPU library.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.configs import deepseek_v2_lite, qwen2_7b
from repro.kernels.flash_decode import certified_decode_attention
from repro.kernels.quant_matmul import (grouped_quant_matmul_format_dispatch,
                                        quant_matmul_format_dispatch)

CFG = qwen2_7b.FULL
D, F, V = CFG.d_model, CFG.d_ff, CFG.vocab
KV = CFG.n_kv_heads * CFG.head_dim
LANES, PREFILL, MAX_SEQ = 8, 384, 1024      # the chip smoke's shapes


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # compiles for a described chip cannot be read back from the
        # persistent cache; keep them out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _shape(sharding, *dims, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_decode_certified_compiles_for_v5e(one_chip):
    G = CFG.n_heads // CFG.n_kv_heads
    text = _compiled_text(
        lambda q, k, v, n, f: certified_decode_attention(
            q, k, v, n, f, force_kernel=True),
        _shape(one_chip, LANES, CFG.n_kv_heads, G, CFG.head_dim),
        _shape(one_chip, LANES, MAX_SEQ, CFG.n_kv_heads, CFG.head_dim),
        _shape(one_chip, LANES, MAX_SEQ, CFG.n_kv_heads, CFG.head_dim),
        _shape(one_chip, LANES, dtype=jnp.int32),
        _shape(one_chip, 3, dtype=jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("M", [LANES, PREFILL], ids=["decode", "prefill"])
@pytest.mark.parametrize("K,N", [(D, D), (D, KV), (D, F), (F, D), (D, V)],
                         ids=["q_o", "k_v", "gate_up", "down", "lm_head"])
def test_quant_matmul_format_compiles_for_v5e(one_chip, M, K, N):
    text = _compiled_text(
        lambda x, w, f: quant_matmul_format_dispatch(x, w, f,
                                                     force_kernel=True),
        _shape(one_chip, M, K), _shape(one_chip, K, N),
        _shape(one_chip, 3, dtype=jnp.int32))
    assert "tpu_custom_call" in text


DS = deepseek_v2_lite.FULL
DS_LANES = 32                       # the benchmark cell's lanes


@pytest.mark.parametrize("M", [DS_LANES, PREFILL], ids=["decode", "prefill"])
@pytest.mark.parametrize("K,N", [
    (DS.d_model, DS.kv_rank + DS.d_rope), (DS.d_model, DS.d_ff),
    (DS.d_ff, DS.d_model),
    (DS.n_shared_experts * DS.moe_d_ff, DS.d_model)],
    ids=["kv_a", "dense_gate_up", "dense_down", "shared_down"])
def test_quant_matmul_format_compiles_off_the_lane_tiling(one_chip, M, K, N):
    """Widths that are no multiple of the 512-deep K block or the 256-wide
    column block: a last block overhangs (576, 10944, 2816)."""
    text = _compiled_text(
        lambda x, w, f: quant_matmul_format_dispatch(x, w, f,
                                                     force_kernel=True),
        _shape(one_chip, M, K), _shape(one_chip, K, N),
        _shape(one_chip, 3, dtype=jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tokens, tm", [(DS_LANES, 8), (1024, 128)],
                         ids=["decode", "prefill"])
@pytest.mark.parametrize("K,N", [(DS.d_model, DS.moe_d_ff),
                                 (DS.moe_d_ff, DS.d_model)],
                         ids=["gate_up", "down"])
def test_grouped_quant_matmul_format_compiles_for_v5e(one_chip, tokens, tm,
                                                      K, N):
    """The routed experts' grouped grid over a stack of four layers of 64
    experts, at the row tiles decode and a 1024-token prefill use."""
    P, E = tokens * DS.top_k, DS.n_experts
    tiles = (P + min(E, P) * (tm - 1)) // tm
    text = _compiled_text(
        lambda x, w, te, m, f: grouped_quant_matmul_format_dispatch(
            x, w, te, m, f, tm=tm, force_kernel=True),
        _shape(one_chip, tiles * tm, K), _shape(one_chip, 4, E, K, N),
        _shape(one_chip, tiles, dtype=jnp.int32),
        _shape(one_chip, 2, dtype=jnp.int32),
        _shape(one_chip, 3, dtype=jnp.int32))
    assert "tpu_custom_call" in text
