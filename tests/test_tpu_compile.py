"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers one kernel through the ``pallas-tpu``
backend at published widths and asserts that the TPU compiler accepted it
and kept the Mosaic kernel (``tpu_custom_call``) in the program. Interpret
mode cannot catch what this does: blocks whose last two dims are neither
(8, 128)-aligned nor whole, shape casts Mosaic refuses, VMEM overruns.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU compiler's library, so under
several test workers only the worker given this file touches it.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api.backends import get_backend
from repro.kernels.quantize import kv_group_size

# (kv heads, query heads per kv head, head_dim, d_model, d_ff)
WIDTHS = {
    "stablelm-1.6b": (32, 1, 64, 2048, 5632),
    "mistral-nemo-12b": (8, 4, 128, 5120, 14336),
}
PRECISIONS = ("fp", "int8", "int4")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler library on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's programs cannot be read back from the persistent
    # cache (there is no device to load them on): keep it off meanwhile
    from jax.experimental.compilation_cache import compilation_cache as cc

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _compile(fn, args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _kv(sharding, lead, hkv, hd, prec, dtype=jnp.bfloat16):
    """(payload, scale) shape structs for one K or V stream."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    if prec == "int4":
        return [sds(lead + (hkv, hd // 2), jnp.int8),
                sds(lead + (hkv, hd // kv_group_size(hd)), jnp.float16)]
    if prec == "int8":
        return [sds(lead + (hkv, hd), jnp.int8),
                sds(lead + (hkv,), jnp.float32)]
    return [sds(lead + (hkv, hd), dtype)]


@pytest.mark.parametrize("prec", PRECISIONS)
@pytest.mark.parametrize("model", sorted(WIDTHS))
def test_paged_decode_compiles(one_chip, model, prec):
    hkv, g, hd, _, _ = WIDTHS[model]
    b, n_blocks, bs, m = 8, 257, 16, 32
    be = get_backend("pallas-tpu")
    q = jax.ShapeDtypeStruct((b, hkv, g, hd), jnp.bfloat16, sharding=one_chip)
    kv = _kv(one_chip, (n_blocks, bs), hkv, hd, prec)
    tables = jax.ShapeDtypeStruct((b, m), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    fn = {"fp": be.paged_decode, "int8": be.paged_qdecode,
          "int4": be.paged_q4decode}[prec]
    _compile(fn, [q, *kv, *kv, tables, pos])


@pytest.mark.parametrize("prec", PRECISIONS)
@pytest.mark.parametrize("model", sorted(WIDTHS))
def test_flash_prefill_compiles(one_chip, model, prec):
    hkv, g, hd, _, _ = WIDTHS[model]
    b, s = 1, 2048
    be = get_backend("pallas-tpu")
    q = jax.ShapeDtypeStruct((b, s, hkv * g, hd), jnp.bfloat16,
                             sharding=one_chip)
    kv = _kv(one_chip, (b, s), hkv, hd, prec)
    fn = {"fp": be.flash_prefill, "int8": be.flash_qprefill,
          "int4": be.flash_q4prefill}[prec]
    _compile(fn, [q, *kv, *kv])


@pytest.mark.parametrize("model", sorted(WIDTHS))
def test_qdecode_compiles(one_chip, model):
    hkv, g, hd, _, _ = WIDTHS[model]
    b, s = 8, 4096
    q = jax.ShapeDtypeStruct((b, hkv, g, hd), jnp.bfloat16, sharding=one_chip)
    kv = _kv(one_chip, (b, s), hkv, hd, "int8")
    bias = jax.ShapeDtypeStruct((b, s), jnp.float32, sharding=one_chip)
    _compile(get_backend("pallas-tpu").qdecode, [q, *kv, *kv, bias])


@pytest.mark.parametrize("precision", ("default", "highest"))
@pytest.mark.parametrize("kind", ("static", "dynamic"))
@pytest.mark.parametrize("model", sorted(WIDTHS))
def test_qmatmul_compiles(one_chip, model, kind, precision):
    """Also under ``default_matmul_precision("highest")``, as an f32
    reference forward would set it: the int8 dot must not inherit it."""
    _, _, _, d, d_ff = WIDTHS[model]
    be = get_backend("pallas-tpu")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x = sds((8, d), jnp.bfloat16)
    w = sds((d, 2 * d_ff), jnp.int8)
    scale = sds((1, 2 * d_ff), jnp.float32)
    with jax.default_matmul_precision(precision):
        if kind == "static":
            _compile(be.qmatmul_static, [x, w, scale, sds((), jnp.float32)])
        else:
            _compile(be.qmatmul_dynamic, [x, w, scale])
