"""CPU guard for ``chip_smoke.py``: its serve-and-compare logic runs here on
the stablelm smoke config, ``pallas-interpret`` against ``ref``; its
``main`` refuses any platform but a TPU."""
from __future__ import annotations

import importlib.util
import pathlib

import jax
import pytest

from repro.api import ModelArtifact
from repro.configs import smoke_config
from repro.models import init_params

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

#: same shape of traffic as the chip run, cut to the smoke config: requests
#: 3 and 4 share a 32-token (two-block) prefix
REQUESTS = ((41, 6), (33, 4), (49, 5), (40, 4), (37, 6))


@pytest.fixture(scope="module")
def artifact():
    cfg = smoke_config("stablelm-1.6b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return ModelArtifact.create(cfg.name, "v0", params, cfg)


@pytest.mark.parametrize("prec", ("fp", "int4"))
def test_serve_and_compare_interpret_vs_ref(artifact, prec):
    art = ModelArtifact.create(
        artifact.name, artifact.version, artifact.params,
        artifact.config.with_overrides(kv_cache_precision=prec))
    report, kern, ref = chip_smoke.serve_and_compare(
        art, "pallas-interpret", "ref", requests=REQUESTS, prefix=32,
        max_len=64)
    assert report["rel"] <= chip_smoke.TOL[prec], report
    assert kern["prefix_hit_tokens"] == ref["prefix_hit_tokens"] == 32
    assert report["decode_compared"] >= 1
    assert [len(t) for t in kern["tokens"]] == [n for _, n in REQUESTS]
    assert 0.0 <= report["greedy_agreement"] <= 1.0


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main([]) == 2
    err = capsys.readouterr().err
    assert "'cpu'" in err


def test_compile_cache_dir_is_fixed_in_checkout(monkeypatch):
    """``enable_compile_cache`` keeps JAX_COMPILATION_CACHE_DIR where it is
    set and otherwise points JAX at ``.jax_cache`` in the checkout."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.DEFAULT_DIR == _PATH.parent / ".jax_cache"
        assert compile_cache.enable_compile_cache() == str(
            compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(
            compile_cache.DEFAULT_DIR)

        jax.config.update("jax_compilation_cache_dir", before)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/user")
        assert compile_cache.enable_compile_cache() == "/set/by/user"
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
