"""Sharding rule units: divisibility guards, quantized-leaf handling, cache
heuristics — all on an abstract mesh (no devices needed)."""
from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

from repro import configs as C
from repro.models.sharding import (cache_spec, checked_spec, data_spec,
                                   _param_rule)

MESH = AbstractMesh((16, 16), ("data", "model"),
                    axis_types=(AxisType.Auto,) * 2)
POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"),
                   axis_types=(AxisType.Auto,) * 3)


def test_checked_spec_drops_indivisible():
    assert checked_spec((10, 32), MESH, "model", None) == P(None, None)
    assert checked_spec((32, 32), MESH, "model", None) == P("model", None)


def test_param_rules():
    cfg = C.get_config("mistral-nemo-12b")
    # column-parallel attention projection (stacked over layers)
    assert _param_rule("layers/attn/wq", (40, 5120, 4096), MESH, cfg) \
        == P(None, None, "model")
    # row-parallel output
    assert _param_rule("layers/attn/wo", (40, 4096, 5120), MESH, cfg) \
        == P(None, "model", None)
    # norms replicate
    assert _param_rule("layers/ln1", (40, 5120), MESH, cfg) == P(None, None)
    # vocab-parallel embedding
    assert _param_rule("embed", (131072, 5120), MESH, cfg) == P("model", None)


def test_param_rules_fsdp_and_experts():
    cfg = C.get_config("kimi-k2-1t-a32b")  # fsdp=True
    spec = _param_rule("layers/moe/wi", (60, 384, 7168, 4096), MESH, cfg)
    assert spec == P(None, "model", "data", None)  # expert + fsdp sharding
    spec = _param_rule("layers/attn/wq", (60, 7168, 8192), MESH, cfg)
    assert spec == P(None, "data", "model")


def test_quantized_leaf_rules():
    cfg = C.get_config("deepseek-7b")
    w = _param_rule("layers/attn/wq/w_int8", (30, 4096, 4096), MESH, cfg)
    assert w == P(None, None, "model")
    s = _param_rule("layers/attn/wq/scale", (30, 1, 4096), MESH, cfg)
    assert s == P(None, None, None)


def test_cache_spec_heuristics():
    # [L, B, S, Hkv, hd]: batch on data, model on seq (kv=8 < 16)
    spec = cache_spec((40, 128, 32768, 8, 128), MESH)
    assert spec == P(None, "data", "model", None, None)
    # kv=32 divisible: model goes to the largest divisible dim (still seq)
    spec = cache_spec((24, 128, 32768, 32, 64), MESH)
    assert spec[1] == "data" and "model" in spec
    # batch=1 (long_500k): batch unshardable -> dropped
    spec = cache_spec((40, 1, 4096, 8, 128), MESH)
    assert spec[1] is None and spec[2] == "model"


def test_data_spec_multipod():
    spec = data_spec((256, 4096), POD)
    assert spec == P(("pod", "data"), None)
    # indivisible batch drops the axes
    assert data_spec((3, 4096), POD) == P(None, None)
