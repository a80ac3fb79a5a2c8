"""Pluggable kernel-backend registry (control-plane API, DESIGN §API).

A ``Backend`` implements the compute primitives the model layers dispatch
to (``qmatmul_static`` / ``qmatmul_dynamic`` / ``quantize_weights`` /
``qdecode``, the paged decode trio, and the fused flash-prefill trio —
fp / int8 / int4 precision tiers for the latter two).
Five backends ship built-in:

    ref              pure-jnp oracles (fast under XLA on CPU)
    pallas-interpret Pallas kernels in interpret mode (CPU-debuggable)
    pallas-tpu       Pallas kernels compiled natively (TPU)
    ref-tp           tensor-parallel twin of ref (host-device test mesh)
    pallas-tpu-tp    tensor-parallel twin of pallas-tpu (chip mesh)

Backend choice is scoped, not global: ``use_backend("ref")`` binds a backend
for the duration of a trace, and ``InferenceSession(..., backend=...)`` binds
one per session, so a single process can serve fp32 on one session and
int8-Pallas on another. The process-wide default follows the platform:
``pallas-tpu`` on a TPU, ``ref`` elsewhere; interpret-mode kernels are
always an explicit choice (``use_backend("pallas-interpret")``).
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, Iterator, List, Optional, Union

import jax

from repro.kernels import autotune as _at
from repro.kernels import dynquant as _dyn
from repro.kernels import flash_prefill as _fp
from repro.kernels import paged_attn as _pa
from repro.kernels import qdecode as _qd
from repro.kernels import qmatmul as _static
from repro.kernels import quantize as _quant
from repro.kernels import ref as _ref


class Backend:
    """Protocol/base for kernel backends. Subclass and ``register_backend``
    to plug in a new implementation (e.g. a GPU Triton port)."""

    name: str = "abstract"

    def qmatmul_static(self, x, w_int8, w_scale, act_scale):
        raise NotImplementedError

    def qmatmul_dynamic(self, x, w_int8, w_scale):
        raise NotImplementedError

    def quantize_weights(self, w):
        raise NotImplementedError

    def qdecode(self, q, k_i8, k_s, v_i8, v_s, bias):
        raise NotImplementedError

    def paged_decode(self, q, k_pool, v_pool, tables, pos):
        raise NotImplementedError

    def paged_qdecode(self, q, k_pool, k_scale, v_pool, v_scale, tables, pos):
        raise NotImplementedError

    def paged_q4decode(self, q, k_pool, k_scale, v_pool, v_scale, tables,
                       pos):
        raise NotImplementedError

    def flash_prefill(self, q, k, v):
        raise NotImplementedError

    def flash_qprefill(self, q, k_i8, k_s, v_i8, v_s):
        raise NotImplementedError

    def flash_q4prefill(self, q, k_i4, k_s, v_i4, v_s):
        raise NotImplementedError

    def __repr__(self) -> str:
        return f"<Backend {self.name}>"


class RefBackend(Backend):
    """Pure-jnp reference implementations — identical semantics to the
    kernels, XLA-compiled (the fast path on CPU hosts)."""

    name = "ref"

    def qmatmul_static(self, x, w_int8, w_scale, act_scale):
        return _ref.qmatmul_static_ref(x, w_int8, w_scale, act_scale)

    def qmatmul_dynamic(self, x, w_int8, w_scale):
        return _ref.qmatmul_dynamic_ref(x, w_int8, w_scale)

    def quantize_weights(self, w):
        return _ref.quantize_ref(w)

    def qdecode(self, q, k_i8, k_s, v_i8, v_s, bias):
        return _ref.qdecode_ref(q, k_i8, k_s, v_i8, v_s, bias)

    def paged_decode(self, q, k_pool, v_pool, tables, pos):
        return _ref.paged_decode_ref(q, k_pool, v_pool, tables, pos)

    def paged_qdecode(self, q, k_pool, k_scale, v_pool, v_scale, tables, pos):
        return _ref.paged_qdecode_ref(q, k_pool, k_scale, v_pool, v_scale,
                                      tables, pos)

    def paged_q4decode(self, q, k_pool, k_scale, v_pool, v_scale, tables,
                       pos):
        return _ref.paged_q4decode_ref(q, k_pool, k_scale, v_pool, v_scale,
                                       tables, pos)

    def flash_prefill(self, q, k, v):
        return _ref.flash_prefill_ref(q, k, v)

    def flash_qprefill(self, q, k_i8, k_s, v_i8, v_s):
        return _ref.flash_qprefill_ref(q, k_i8, k_s, v_i8, v_s)

    def flash_q4prefill(self, q, k_i4, k_s, v_i4, v_s):
        return _ref.flash_q4prefill_ref(q, k_i4, k_s, v_i4, v_s)


class PallasBackend(Backend):
    """Pallas kernels; ``interpret=True`` runs them on CPU."""

    def __init__(self, name: str, interpret: bool):
        self.name = name
        self.interpret = interpret

    def qmatmul_static(self, x, w_int8, w_scale, act_scale):
        return _static.qmatmul_static(x, w_int8, w_scale, act_scale,
                                      interpret=self.interpret)

    def qmatmul_dynamic(self, x, w_int8, w_scale):
        return _dyn.qmatmul_dynamic(x, w_int8, w_scale,
                                    interpret=self.interpret)

    def quantize_weights(self, w):
        return _quant.quantize_weights(w, interpret=self.interpret)

    def qdecode(self, q, k_i8, k_s, v_i8, v_s, bias):
        return _qd.qdecode_attention(q, k_i8, k_s, v_i8, v_s, bias,
                                     interpret=self.interpret)

    def paged_decode(self, q, k_pool, v_pool, tables, pos):
        return _pa.paged_decode_attention(q, k_pool, v_pool, tables, pos,
                                          interpret=self.interpret)

    def paged_qdecode(self, q, k_pool, k_scale, v_pool, v_scale, tables, pos):
        return _pa.paged_qdecode_attention(q, k_pool, k_scale, v_pool,
                                           v_scale, tables, pos,
                                           interpret=self.interpret)

    def paged_q4decode(self, q, k_pool, k_scale, v_pool, v_scale, tables,
                       pos):
        return _pa.paged_q4decode_attention(q, k_pool, k_scale, v_pool,
                                            v_scale, tables, pos,
                                            interpret=self.interpret)

    def flash_prefill(self, q, k, v):
        # block shapes come from the deterministic autotuner (winner table
        # keyed per backend/head-dim/precision/seq bucket; REPRO_TILE_* pins)
        bq, bk = _at.tile_config(self.name, "flash_prefill", q.shape[-1],
                                 "fp32", q.shape[1])
        return _fp.flash_prefill_attention(q, k, v, block_q=bq, block_k=bk,
                                           interpret=self.interpret)

    def flash_qprefill(self, q, k_i8, k_s, v_i8, v_s):
        bq, bk = _at.tile_config(self.name, "flash_qprefill", q.shape[-1],
                                 "int8", q.shape[1])
        return _fp.flash_qprefill_attention(q, k_i8, k_s, v_i8, v_s,
                                            block_q=bq, block_k=bk,
                                            interpret=self.interpret)

    def flash_q4prefill(self, q, k_i4, k_s, v_i4, v_s):
        bq, bk = _at.tile_config(self.name, "flash_q4prefill", q.shape[-1],
                                 "int4", q.shape[1])
        return _fp.flash_q4prefill_attention(q, k_i4, k_s, v_i4, v_s,
                                             block_q=bq, block_k=bk,
                                             interpret=self.interpret)


class TPBackend(Backend):
    """Tensor-parallel twin of an inner backend (mesh-aware serving).

    The compute primitives delegate 1:1 to the inner backend: under TP the
    engine wraps the model entry points in shard_map
    (``repro.serving.sharded.TPContext``), so by the time a primitive runs
    it already sees this shard's kv-head slice of q / pools / scales — the
    per-shard math IS the single-device math, and the cross-shard combine
    lives at the model's wo sites (``layers.row_combine``), not here.

    Pinning a ``*-tp`` backend is the transparent opt-in:
    ``ContinuousBatchingEngine`` (and the fleet ``EnginePool``) shard the
    engine with ``default_tp`` shards unless an explicit ``tp=N`` /
    ``EngineConfig(tp=N)`` overrides it.
    """

    def __init__(self, name: str, inner: str, default_tp: int = 2):
        self.name = name
        self.inner_name = inner
        self.default_tp = default_tp

    @property
    def inner(self) -> "Backend":
        return get_backend(self.inner_name)

    def qmatmul_static(self, x, w_int8, w_scale, act_scale):
        return self.inner.qmatmul_static(x, w_int8, w_scale, act_scale)

    def qmatmul_dynamic(self, x, w_int8, w_scale):
        return self.inner.qmatmul_dynamic(x, w_int8, w_scale)

    def quantize_weights(self, w):
        return self.inner.quantize_weights(w)

    def qdecode(self, q, k_i8, k_s, v_i8, v_s, bias):
        return self.inner.qdecode(q, k_i8, k_s, v_i8, v_s, bias)

    def paged_decode(self, q, k_pool, v_pool, tables, pos):
        return self.inner.paged_decode(q, k_pool, v_pool, tables, pos)

    def paged_qdecode(self, q, k_pool, k_scale, v_pool, v_scale, tables, pos):
        return self.inner.paged_qdecode(q, k_pool, k_scale, v_pool, v_scale,
                                        tables, pos)

    def paged_q4decode(self, q, k_pool, k_scale, v_pool, v_scale, tables,
                       pos):
        return self.inner.paged_q4decode(q, k_pool, k_scale, v_pool, v_scale,
                                         tables, pos)

    def flash_prefill(self, q, k, v):
        return self.inner.flash_prefill(q, k, v)

    def flash_qprefill(self, q, k_i8, k_s, v_i8, v_s):
        return self.inner.flash_qprefill(q, k_i8, k_s, v_i8, v_s)

    def flash_q4prefill(self, q, k_i4, k_s, v_i4, v_s):
        return self.inner.flash_q4prefill(q, k_i4, k_s, v_i4, v_s)


# ------------------------------------------------------------------ #
# Registry
# ------------------------------------------------------------------ #
_BACKENDS: Dict[str, Backend] = {}


def register_backend(backend: Backend, name: Optional[str] = None) -> Backend:
    _BACKENDS[name or backend.name] = backend
    return backend


def available_backends() -> List[str]:
    return sorted(_BACKENDS)


def get_backend(name: Union[str, Backend]) -> Backend:
    if isinstance(name, Backend):
        return name
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel backend {name!r}; registered backends: "
            f"{', '.join(available_backends())}") from None


register_backend(RefBackend())
register_backend(PallasBackend("pallas-interpret", interpret=True))
register_backend(PallasBackend("pallas-tpu", interpret=False))
# tensor-parallel twins: same kernels, engine shards the model around them
register_backend(TPBackend("ref-tp", inner="ref"))
register_backend(TPBackend("pallas-tpu-tp", inner="pallas-tpu"))


# ------------------------------------------------------------------ #
# Default + scoped selection
# ------------------------------------------------------------------ #
_DEFAULT: List[Optional[Backend]] = [None]   # resolved lazily, cached
_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "repro_active_backend", default=None)


def default_backend() -> Backend:
    """TPU -> native Pallas; anything else -> ref (XLA-fast). Resolved
    once, then cached."""
    if _DEFAULT[0] is None:
        _DEFAULT[0] = get_backend("pallas-tpu" if jax.default_backend()
                                  == "tpu" else "ref")
    return _DEFAULT[0]


def set_default_backend(name: Optional[Union[str, Backend]]) -> None:
    """Override (or with None: re-resolve) the process-wide default."""
    _DEFAULT[0] = get_backend(name) if name is not None else None


def current_backend() -> Backend:
    """The backend in scope: innermost ``use_backend`` binding, else the
    process default. Resolved at *trace* time by the quantized layers, so a
    jit-compiled function bakes in whichever backend was bound when traced."""
    active = _ACTIVE.get()
    return active if active is not None else default_backend()


@contextlib.contextmanager
def use_backend(name: Optional[Union[str, Backend]]) -> Iterator[Backend]:
    """Bind a backend for the dynamic extent of the block. ``None`` is a
    no-op (keeps whatever is currently in scope)."""
    if name is None:
        yield current_backend()
        return
    token = _ACTIVE.set(get_backend(name))
    try:
        yield _ACTIVE.get()
    finally:
        _ACTIVE.reset(token)
