"""Tensor-parallel sharded serving: one model across many chips.

``TPContext`` wraps every serving entry point of ``repro.models`` in
``shard_map`` over a ``("data", "model")`` mesh (``launch.mesh.make_tp_mesh``;
data=1 — replicas are the fleet's job). Inside the body the *unmodified*
model code runs on a local view:

  params   wq/wk/wv/w_uq/w_ukv/wi column-sharded on "model" (contiguous
           chunks == head groups), wo row-sharded ("psum") or replicated
           ("exact"); everything else — embeddings, norms, MLA
           down-projections — replicated (``sharding.tp_param_specs``).
  cfg      heads / kv-heads / d_ff divided by tp (``tp_local_config``), so
           reshape-by-head code and the hot-path kernels (``paged_attn``
           decode, the verify twins, ``flash_prefill``) are mesh-aware by
           construction: each shard runs them on its own head slice, in
           every KV precision tier (int8/int4 scale rows ride the same
           head axis and stay shard-local).
  caches   GQA payload+scale leaves sharded on the kv-head axis (dense and
           paged pools alike); MLA latent caches are head-free and stay
           replicated (``sharding.tp_cache_specs``). Block tables are
           host-side metadata: replicated.

The only cross-shard traffic is the wo-site combine
(``layers.row_combine``): "exact" all_gathers head/ff slices and applies
the full weight — greedy streams are bit-identical to tp=1, the CI
contract — while "psum" keeps wo row-parallel and reduces the [., d]
partials (the production path; logits agree to fp tolerance).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import transformer as _m
from repro.models.config import ModelConfig
from repro.models.sharding import (tp_cache_specs, tp_param_specs, tp_region)

def _shard_map(f, mesh, in_specs, out_specs):
    """shard_map with replication checking off (the "exact" combine produces
    provably-replicated outputs the checker cannot see)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# --------------------------------------------------------------------- #
# Support gate
# --------------------------------------------------------------------- #
def _has_quantized_leaves(tree) -> bool:
    if isinstance(tree, dict):
        if "w_int8" in tree or "w_int4" in tree:
            return True
        return any(_has_quantized_leaves(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return any(_has_quantized_leaves(v) for v in tree)
    return False


def tp_unsupported_reason(cfg: ModelConfig, tp: int,
                          params=None) -> Optional[str]:
    """None when ``(cfg, tp)`` can serve tensor-parallel, else why not."""
    if tp < 2:
        return None
    if cfg.attention not in ("full", "mla"):
        return f"attention={cfg.attention!r} (dense GQA/MLA stacks only)"
    if cfg.window:
        return "sliding-window attention"
    if getattr(cfg, "n_experts", 0):
        return "MoE layers (expert parallelism is moe_ffn_sharded's job)"
    if cfg.n_codebooks > 1:
        return "multi-codebook heads"
    if cfg.frontend != "none":
        return f"frontend={cfg.frontend!r}"
    if cfg.n_heads % tp:
        return f"n_heads={cfg.n_heads} not divisible by tp={tp}"
    if cfg.attention != "mla" and cfg.n_kv_heads % tp:
        return f"n_kv_heads={cfg.n_kv_heads} not divisible by tp={tp}"
    if cfg.d_ff % tp:
        return f"d_ff={cfg.d_ff} not divisible by tp={tp}"
    if params is not None and _has_quantized_leaves(params):
        return "quantized weight leaves (TP shards fp weights only; " \
               "quantized KV-cache tiers are fully supported)"
    return None


def tp_local_config(cfg: ModelConfig, tp: int) -> ModelConfig:
    """The per-shard view: heads and MLP width divided by tp. ``head_dim``
    is pinned explicitly so ``resolved_head_dim`` cannot drift when
    ``d_model / n_heads`` changes under it."""
    over: Dict[str, Any] = {"n_heads": cfg.n_heads // tp,
                            "head_dim": cfg.resolved_head_dim,
                            "d_ff": cfg.d_ff // tp}
    if cfg.attention != "mla":
        over["n_kv_heads"] = cfg.n_kv_heads // tp
    else:
        over["n_kv_heads"] = max(cfg.n_kv_heads // tp, 1)
    return cfg.with_overrides(**over)


# --------------------------------------------------------------------- #
# Host-side weight prep
# --------------------------------------------------------------------- #
def _wi_permuter(ndim: int, sharding, tp: int):
    """Jitted column permutation of one ``wi`` leaf, making each shard's
    fused gate|up slice locally splittable: shard s gets [gate_s | up_s]
    instead of a naive contiguous chunk (which would hand shard 0 all-gate
    and shard tp-1 all-up). Columns ``[gate | up]`` viewed as ``[2, tp,
    ff / tp]`` become ``[tp, 2, ff / tp]``. On column-sharded weights that
    is an all-to-all; it runs one layer at a time and keeps the leaf's
    sharding, so no device ever holds more than its own slice plus one
    layer's."""
    inner = None
    if isinstance(sharding, NamedSharding):
        spec = tuple(sharding.spec) + (None,) * (ndim - len(sharding.spec))
        inner = NamedSharding(sharding.mesh, P(*spec[-2:]))

    def one(w):
        d, two_ff = w.shape
        w = w.reshape(d, 2, tp, two_ff // (2 * tp))
        w = jnp.swapaxes(w, 1, 2).reshape(d, two_ff)
        return w if inner is None else jax.lax.with_sharding_constraint(
            w, inner)

    def permute(wi):
        return jax.lax.map(one, wi) if wi.ndim > 2 else one(wi)

    return jax.jit(permute, out_shardings=sharding)


def permute_wi_for_tp(params, tp: int):
    """Permute every MLP ``wi`` leaf's fused gate|up columns so that after
    column-sharding, shard-local ``jnp.split(gu, 2)`` in ``swiglu`` stays a
    gate/up split AND the all-gathered hidden comes back in natural chunk
    order (so the unpermuted wo rows line up in both combine modes)."""

    def rule(path, leaf):
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        if len(keys) >= 2 and keys[-2] == "mlp" and keys[-1] == "wi":
            return _wi_permuter(leaf.ndim, getattr(leaf, "sharding", None),
                                tp)(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(rule, params)


# --------------------------------------------------------------------- #
# TPContext — the engine-facing wrapper
# --------------------------------------------------------------------- #
class TPContext:
    """Shard-mapped twins of the serving entry points, one mesh per engine.

    All wrappers keep the exact calling convention the scheduler binds
    (cfg captured here), so enabling TP is a function-table swap — no
    call-site changes.
    """

    def __init__(self, cfg: ModelConfig, tp: int, combine: str = "exact",
                 mesh=None, params=None):
        why = tp_unsupported_reason(cfg, tp, params)
        if why is not None:
            raise ValueError(f"tensor-parallel serving unsupported: {why}")
        if mesh is None:
            from repro.launch.mesh import make_tp_mesh

            mesh = make_tp_mesh(tp)
        if mesh.shape["model"] != tp:
            raise ValueError(f"mesh model axis {mesh.shape['model']} != tp={tp}")
        self.cfg = cfg
        self.tp = tp
        self.combine = combine
        self.mesh = mesh
        self.local_cfg = tp_local_config(cfg, tp)
        self._pspecs = None

    # -------------------------- placement ------------------------------ #
    def shard_params(self, params):
        """Place every leaf per its TP spec (one transfer at engine init —
        the jitted entry points then see already-resident shards; leaves
        already placed so stay where they are), then permute the
        fused-MLP columns on the mesh."""
        self._pspecs = tp_param_specs(params, self.mesh, self.combine)
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self._pspecs)
        return permute_wi_for_tp(jax.device_put(params, shardings), self.tp)

    def param_specs(self, params):
        if self._pspecs is None:
            self._pspecs = tp_param_specs(params, self.mesh, self.combine)
        return self._pspecs

    def cache_specs(self, caches):
        return tp_cache_specs(self.cfg, caches, self.mesh)

    def shard_cache(self, caches):
        """Place a dense cache / paged pool tree: GQA leaves split on the
        kv-head axis (per-shard HBM = 1/tp of the pool), MLA replicated."""
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self.cache_specs(caches))
        return jax.device_put(caches, shardings)

    # -------------------------- entry points --------------------------- #
    def _wrap(self, body, in_specs, out_specs):
        return _shard_map(body, self.mesh, in_specs, out_specs)

    def decode_step(self, params, caches, tokens, pos):
        lcfg, tp, combine = self.local_cfg, self.tp, self.combine

        def body(p, c, t, pz):
            with tp_region(tp, combine):
                return _m.decode_step(p, c, t, pz, lcfg)

        cspecs = self.cache_specs(caches)
        fn = self._wrap(body,
                        in_specs=(self.param_specs(params), cspecs, P(), P()),
                        out_specs=(P(), cspecs))
        return fn(params, caches, tokens, pos)

    def verify_step(self, params, caches, tokens, pos):
        lcfg, tp, combine = self.local_cfg, self.tp, self.combine

        def body(p, c, t, pz):
            with tp_region(tp, combine):
                return _m.verify_step(p, c, t, pz, lcfg)

        cspecs = self.cache_specs(caches)
        fn = self._wrap(body,
                        in_specs=(self.param_specs(params), cspecs, P(), P()),
                        out_specs=(P(), cspecs))
        return fn(params, caches, tokens, pos)

    def decode_step_paged(self, params, pools, tokens, pos, tables):
        lcfg, tp, combine = self.local_cfg, self.tp, self.combine

        def body(p, c, t, pz, tb):
            with tp_region(tp, combine):
                return _m.decode_step_paged(p, c, t, pz, tb, lcfg)

        cspecs = self.cache_specs(pools)
        fn = self._wrap(body,
                        in_specs=(self.param_specs(params), cspecs,
                                  P(), P(), P()),
                        out_specs=(P(), cspecs))
        return fn(params, pools, tokens, pos, tables)

    def verify_step_paged(self, params, pools, tokens, pos, tables):
        lcfg, tp, combine = self.local_cfg, self.tp, self.combine

        def body(p, c, t, pz, tb):
            with tp_region(tp, combine):
                return _m.verify_step_paged(p, c, t, pz, tb, lcfg)

        cspecs = self.cache_specs(pools)
        fn = self._wrap(body,
                        in_specs=(self.param_specs(params), cspecs,
                                  P(), P(), P()),
                        out_specs=(P(), cspecs))
        return fn(params, pools, tokens, pos, tables)

    def prefill(self, params, batch, n_valid, pad_to: int):
        lcfg, tp, combine = self.local_cfg, self.tp, self.combine

        def body(p, b, nv):
            with tp_region(tp, combine):
                return _m.prefill(p, b, lcfg, pad_to=pad_to, n_valid=nv)

        bsz = int(np.shape(batch["tokens"])[0])
        out_cache = jax.eval_shape(
            lambda: _m.init_cache(self.cfg, bsz, pad_to))
        fn = self._wrap(body,
                        in_specs=(self.param_specs(params), P(), P()),
                        out_specs=(P(), self.cache_specs(out_cache)))
        return fn(params, batch, n_valid)

    def prefill_paged(self, params, pools, batch, n_valid, tables):
        lcfg, tp, combine = self.local_cfg, self.tp, self.combine

        def body(p, c, b, nv, tb):
            with tp_region(tp, combine):
                return _m.prefill_paged(p, c, b, nv, tb, lcfg)

        cspecs = self.cache_specs(pools)
        fn = self._wrap(body,
                        in_specs=(self.param_specs(params), cspecs,
                                  P(), P(), P()),
                        out_specs=(P(), cspecs))
        return fn(params, pools, batch, n_valid, tables)

    def prefill_logits(self, params, batch):
        """Last-position prefill logits — parity-test / debug helper."""
        s = int(np.shape(batch["tokens"])[1])
        logits, _ = self.prefill(params, batch,
                                 jnp.asarray(s, jnp.int32), pad_to=s + 1)
        return logits
