"""Logical-axis sharding rules (DESIGN.md §5).

Mesh axes: ("data", "model") single-pod, ("pod", "data", "model") multi-pod.
Batch-like logical axes map to every non-model axis; tensor-parallel axes map
to "model"; MoE expert dims map to "model" (expert parallelism); big archs
additionally shard weight input dims over "data" (FSDP).

Everything is *shape-checked*: an axis is only assigned if the dim is
divisible by the mesh-axis size, so the same rules serve the 2-device test
mesh and the 512-chip production mesh.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import re
from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.models.config import ModelConfig


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _fits(shape, dim: int, mesh: Mesh, axes) -> bool:
    return dim < len(shape) and shape[dim] % _axis_size(mesh, axes) == 0


def checked_spec(shape, mesh: Mesh, *entries) -> P:
    """Build a PartitionSpec, dropping any entry whose dim isn't divisible."""
    out = []
    for i, e in enumerate(entries):
        out.append(e if e and _fits(shape, i, mesh, e) else None)
    return P(*out)


# --------------------------------------------------------------------- #
# Parameter rules: ordered (regex on tree path, spec entries builder)
# --------------------------------------------------------------------- #
def _param_rule(path: str, shape, mesh: Mesh, cfg: ModelConfig) -> P:
    b = batch_axes(mesh)
    fsdp = "data" if (cfg.fsdp and "data" in mesh.axis_names) else None
    nd = len(shape)

    # quantized leaves: w_int8 shards like its parent weight; scales replicate
    if path.endswith(("/w_int8", "/w_int4")):
        path = path[: -len("/w_int8")]
    elif re.search(r"/(scale|act_scale|zero)$", path):
        return P(*([None] * nd))

    def spec(*tail):
        """Pad with leading Nones for stacked-layer dims."""
        lead = (None,) * (nd - len(tail))
        return checked_spec(shape, mesh, *lead, *tail)

    if re.search(r"(embed|extra_embeds)$", path):
        return spec("model", fsdp)                    # [V, d] vocab-parallel
    if re.search(r"(unembed|out_heads)$", path):
        return spec(fsdp, "model")                    # [d, V]
    if re.search(r"moe/(wi|wo)$", path):
        return spec("model", fsdp, None)              # [E, ., .] expert-parallel
    if re.search(r"router$", path):
        return spec(None, None)
    if re.search(r"(wq|wk|wv|w_uq|w_ukv|wi|w_in|w_x|w_gate|shared_wi|frontend_proj)$", path):
        return spec(fsdp, "model")                    # column-parallel [d, X]
    if re.search(r"(wo|w_out|shared_wo)$", path):
        return spec("model", fsdp)                    # row-parallel [X, d]
    if re.search(r"(w_dq|w_dkv|w_kr)$", path):
        return spec(fsdp, None)                       # low-rank down-proj
    if re.search(r"conv_w$", path):
        return spec(None, "model")                    # [W, C] channel-parallel
    if re.search(r"(A_log|D|dt_bias)$", path):
        return spec("model")                          # per-head [H]
    if re.search(r"(wa|wi_gate)$", path) and nd >= 3:
        return spec(None, None, None)                 # block-diag gates: replicate
    return P(*([None] * nd))                          # norms, biases, lam, ...


def param_specs(cfg: ModelConfig, shapes) -> "jax.tree_util.PyTreeDef":
    """shapes: pytree of ShapeDtypeStruct (jax.eval_shape of init)."""
    mesh = _ambient_mesh()

    def rule(path, leaf):
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        return _param_rule(pstr, leaf.shape, mesh, cfg)

    return jax.tree_util.tree_map_with_path(rule, shapes)


# --------------------------------------------------------------------- #
# Batch / cache / activation specs
# --------------------------------------------------------------------- #
def data_spec(shape, mesh: Mesh) -> P:
    """Batch-first arrays: [B, ...] -> batch on every non-model axis."""
    b = batch_axes(mesh)
    return checked_spec(shape, mesh, b, *([None] * (len(shape) - 1)))


def cache_spec(shape, mesh: Mesh, stacked: bool = True) -> P:
    """Cache leaves are [L, B, ...] (stacked) — greedy assignment:
    batch axes to the batch dim if divisible, then "model" to the largest
    remaining divisible dim (kv-heads, seq, or channel)."""
    b = batch_axes(mesh)
    entries: list = [None] * len(shape)
    bdim = 1 if stacked else 0
    if _fits(shape, bdim, mesh, b):
        entries[bdim] = b
    # place "model" on the largest divisible remaining dim (prefer later dims)
    cand = [
        (shape[i], i)
        for i in range(bdim + 1, len(shape))
        if shape[i] % _axis_size(mesh, "model") == 0 and shape[i] >= _axis_size(mesh, "model")
    ]
    if cand:
        _, i = max(cand)
        entries[i] = "model"
    return P(*entries)


def cache_specs(mesh: Mesh, cache_shapes):
    return jax.tree.map(lambda l: cache_spec(l.shape, mesh), cache_shapes)


def _ambient_mesh() -> Mesh:
    m = jax.sharding.get_abstract_mesh()
    return m


# --------------------------------------------------------------------- #
# Tensor-parallel trace state (serving TP via shard_map)
# --------------------------------------------------------------------- #
# ``serving.sharded`` wraps the model entry points in shard_map and traces
# the body under ``tp_region``: inside, the model runs on a *local* cfg
# (heads / d_ff divided by tp) and the wo-site combine in ``layers`` reads
# this state to emit the cross-shard collective. Outside a region the state
# is None and every combine degrades to a plain ``linear`` — single-device
# callers never pay for TP.

@dataclasses.dataclass(frozen=True)
class TPState:
    tp: int                 # shard count over the "model" mesh axis
    combine: str            # "exact" (all_gather) | "psum" (row-parallel)
    axis: str = "model"     # mesh axis name the collectives run over


_TP_STATE: contextvars.ContextVar[Optional[TPState]] = contextvars.ContextVar(
    "repro_tp_state", default=None)


def tp_state() -> Optional[TPState]:
    """The active ``TPState`` (inside a shard_map body trace) or None."""
    return _TP_STATE.get()


@contextlib.contextmanager
def tp_region(tp: int, combine: str = "exact", axis: str = "model"):
    """Scope marking a shard_map body trace as tensor-parallel."""
    if combine not in ("exact", "psum"):
        raise ValueError(f"unknown TP combine mode {combine!r} "
                         "(expected 'exact' or 'psum')")
    token = _TP_STATE.set(TPState(tp, combine, axis))
    try:
        yield
    finally:
        _TP_STATE.reset(token)


# --------------------------------------------------------------------- #
# Tensor-parallel param / cache specs (shard_map in_specs)
# --------------------------------------------------------------------- #
#: attention / MLP input-side projections: column-parallel (last dim is a
#: head-or-ff concat, contiguous chunks = per-shard head groups). ``wi`` is
#: only safe because the engine pre-permutes its fused gate|up columns
#: (``serving.sharded.permute_wi_for_tp``) so each shard's local split
#: yields [gate_s | up_s].
_TP_COL_RE = re.compile(r"(wq|wk|wv|w_uq|w_ukv|wi)$")
#: output-side projections: row-parallel in "psum" mode, replicated in
#: "exact" mode (the gathered activations need the full weight).
_TP_ROW_RE = re.compile(r"(wo)$")


def tp_param_spec(path: str, shape, mesh: Mesh, combine: str = "exact") -> P:
    """shard_map in_spec for one param leaf under serving TP.

    Unlike ``_param_rule`` (GSPMD hints for training) these are *manual*
    shard_map specs: only head/ff-parallel dims shard; everything else —
    embeddings, norms, MLA down-projections, the residual stream — stays
    replicated so per-shard model code sees full-width activations.
    """
    nd = len(shape)
    if _TP_COL_RE.search(path) and "moe" not in path:
        return checked_spec(shape, mesh, *([None] * (nd - 1)), "model")
    if _TP_ROW_RE.search(path) and "moe" not in path:
        if combine == "exact":
            return P(*([None] * nd))
        return checked_spec(shape, mesh, *([None] * (nd - 2)), "model", None)
    return P(*([None] * nd))


def tp_param_specs(params, mesh: Mesh, combine: str = "exact"):
    """Pytree of shard_map in_specs matching ``params``' structure."""

    def rule(path, leaf):
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        return tp_param_spec(pstr, leaf.shape, mesh, combine)

    return jax.tree_util.tree_map_with_path(rule, params)


def tp_cache_spec(cfg: ModelConfig, shape, mesh: Mesh) -> P:
    """shard_map spec for one KV-cache / paged-pool leaf under serving TP.

    GQA leaves — dense ``[L, B, cl, Hkv, ...]`` and paged ``[L, N, bs,
    Hkv, ...]`` payloads plus their int8/int4 scale rows — all carry the
    kv-head axis at dim 3: shard it. MLA caches (``c_kv``/``k_rope``) are
    head-free latent projections shared by every head shard: replicate.
    """
    nd = len(shape)
    if (cfg.attention != "mla" and nd >= 4
            and shape[3] == cfg.n_kv_heads):
        return checked_spec(shape, mesh, None, None, None, "model",
                            *([None] * (nd - 4)))
    return P(*([None] * nd))


def tp_cache_specs(cfg: ModelConfig, caches, mesh: Mesh):
    """Pytree of shard_map specs matching a cache / pool tree."""
    return jax.tree.map(lambda leaf: tp_cache_spec(cfg, leaf.shape, mesh),
                        caches)


def constrain(x: jax.Array, *entries) -> jax.Array:
    """Sharding constraint that is a no-op outside a mesh context and
    inside a shard_map body (manual axes: the body holds its local shard).

    Entries use logical names: "batch" -> all non-model axes, "model".
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not mesh.axis_names or any(
            t == AxisType.Manual for t in mesh.axis_types):
        return x
    resolved = []
    for e in entries:
        if e == "batch":
            resolved.append(batch_axes(mesh))
        else:
            resolved.append(e)
    spec = checked_spec(x.shape, mesh, *resolved)
    return jax.lax.with_sharding_constraint(x, spec)
