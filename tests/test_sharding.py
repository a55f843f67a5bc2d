"""Sharding rule engine: divisibility fallbacks and policy behaviour.

Uses a mock mesh (the helpers only touch axis_names/devices.shape) so the
rules are testable without 256 devices.
"""
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.parallel import sharding as sh


class MockMesh:
    def __init__(self, shape, axes):
        self.axis_names = axes
        self.devices = np.empty(shape, dtype=object)


SINGLE = MockMesh((16, 16), ("data", "model"))
MULTI = MockMesh((2, 16, 16), ("pod", "data", "model"))


def test_greedy_assigns_model_to_biggest_divisible_dim():
    spec = sh._greedy_param_spec((4096, 16384), SINGLE, stacked=False)
    assert spec == P("data", "model")  # 16384 biggest → model; 4096 → data


def test_greedy_respects_stacked_layer_dim():
    spec = sh._greedy_param_spec((48, 4096, 16384), SINGLE, stacked=True)
    assert spec[0] is None


def test_greedy_small_tensors_replicate():
    spec = sh._greedy_param_spec((128,), SINGLE, stacked=False)
    assert spec == P(None)


def test_greedy_indivisible_dims_skipped():
    # 30 not divisible by 16 on either axis → replicate that dim
    spec = sh._greedy_param_spec((30, 1 << 20), SINGLE, stacked=False)
    assert spec[0] is None and spec[1] == "model"


def test_model_only_never_uses_data():
    spec = sh._greedy_param_spec((8192, 8192), SINGLE, stacked=False,
                                 axes=("model",))
    assert "data" not in tuple(spec) and "model" in tuple(spec)


def test_batch_spec_prefers_batch_then_seq():
    assert sh.batch_spec(SINGLE, 256, 4096) == P(("data",), None)
    # batch 1 can't take the axis → sequence parallelism fallback
    assert sh.batch_spec(SINGLE, 1, 524288) == P(None, ("data",))
    # multi-pod: both dp axes over batch when divisible
    assert sh.batch_spec(MULTI, 256, 4096) == P(("pod", "data"), None)


def test_cache_spec_gqa_heads_divisible():
    # [L,B,K,S,Dh] with K=16 divisible by model → heads sharded
    spec = sh.cache_spec(SINGLE, (46, 128, 16, 32768, 128), "gqa")
    assert spec[2] == "model" and spec[1] == "data"


def test_cache_spec_gqa_seq_fallback():
    # K=8 not divisible by 16 → KV-sequence over model (flash-style)
    spec = sh.cache_spec(SINGLE, (28, 128, 8, 32768, 128), "gqa")
    assert spec[3] == "model" and spec[2] is None


def test_cache_spec_batch1_long_context():
    spec = sh.cache_spec(SINGLE, (24, 1, 8, 524288, 128), "gqa")
    # batch 1: sequence takes both axes
    assert spec[3] in (("data", "model"), "model")


def test_cache_spec_mla_latent():
    spec = sh.cache_spec(SINGLE, (62, 128, 32768, 256), "mla")
    assert spec[1] == "data" and spec[2] == "model"
