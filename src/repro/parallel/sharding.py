"""Sharding rules: parameter/input/cache PartitionSpecs per architecture.

MaxText-style named rules: every parameter name maps to a PartitionSpec
over the ("pod", "data", "model") production mesh; GSPMD propagates the
rest.  DP composes ("pod","data"); TP/EP live on "model".

Divisibility fallbacks (DESIGN.md §8) are applied here: dims that don't
divide the axis size fall back to contraction-dim or replicated layouts,
so every assigned arch lowers on the 16x16 and 2x16x16 meshes.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.model_config import ModelSpec
from repro.kernels import page_layout


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def dp_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


#: (name, shape, tp) triples whose replicate-fallback warning already
#: fired — module-level so tp sweeps (one ShardingRules per engine)
#: warn once per distinct degradation, not once per sweep point.
_PAGED_FALLBACK_WARNED: set = set()


class ShardingRules:
    """Produces NamedShardings for params / batches / caches of one arch."""

    def __init__(self, mesh: Mesh, spec: ModelSpec,
                 expert_layout: str = "ep", fsdp: bool = False,
                 cache_layout: str = "auto"):
        self.mesh = mesh
        self.spec = spec
        self.tp = _axis_size(mesh, "model")
        self.dp = int(np.prod([_axis_size(mesh, a) for a in dp_axes(mesh)]))
        self.expert_layout = expert_layout        # "ep" | "tp" (hillclimb knob)
        self.fsdp = fsdp                          # 2-D weight sharding over data
        self.cache_layout = cache_layout          # auto | seq | headdim

    # -- parameters ---------------------------------------------------------
    def param_pspec(self, name: str, shape: Tuple[int, ...]) -> P:
        sp, tp = self.spec, self.tp
        base = name.split("/")[-1]

        def col(dim_in, dim_out):                  # column-parallel
            if _div(dim_out, tp):
                return P(None, "model")
            if _div(dim_in, tp):
                return P("model", None)            # row-parallel fallback
            return P(None, None)

        if base == "embed":
            return P("model", None) if _div(shape[0], tp) else P(None, "model")
        if base == "head":
            return P(None, "model") if _div(shape[1], tp) else P("model", None)
        if base in ("wq", "cross_wq"):
            return col(shape[0], shape[1])
        if base in ("wk", "wv", "cross_wk", "cross_wv"):
            # GQA: kv_dim often < tp -> replicate (small) or row-parallel
            return P(None, "model") if _div(shape[1], tp) else P(None, None)
        if base in ("wo", "cross_wo"):
            if _div(shape[0], tp):
                return P("model", None)
            return P(None, "model") if _div(shape[1], tp) else P(None, None)
        if base == "mlp_wi" or base == "shared_wi":
            return col(shape[0], shape[1])
        if base == "mlp_wo" or base == "shared_wo":
            return P("model", None) if _div(shape[0], tp) else P(None, None)
        if base == "experts_wi":
            if self.expert_layout == "ep" and _div(shape[0], tp):
                return P("model", None, None)
            return P(None, None, "model") if _div(shape[2], tp) else P(None, None, None)
        if base == "experts_wo":
            if self.expert_layout == "ep" and _div(shape[0], tp):
                return P("model", None, None)
            return P(None, "model", None) if _div(shape[1], tp) else P(None, None, None)
        if base == "router_w":
            return P(None, None)
        if base == "ssm_in_proj":
            return P("model", None) if _div(shape[0], tp) else P(None, None)
        if base == "ssm_out_proj":
            return P("model", None) if _div(shape[0], tp) else P(None, None)
        if base in ("ml_up", "sl_wx", "sl_wr"):
            return col(shape[0], shape[1])
        if base in ("ml_q", "ml_k", "ml_v"):
            return col(shape[0], shape[1])
        if base == "ml_down":
            return P("model", None) if _div(shape[0], tp) else P(None, None)
        if base == "vision_proj":
            return P(None, "model") if _div(shape[1], tp) else P(None, None)
        return P()                                  # norms, gates, 1-D: replicate

    def _with_layer_dim(self, pspec: P, stacked: bool) -> P:
        return P(None, *pspec) if stacked else pspec

    def _path_info(self, path):
        """(param name, stacked?, qt_part) from a tree_flatten_with_path path.
        qt_part: None for plain arrays; 0=q / 1=scale / 2=zero for
        QuantizedTensor children."""
        name, stacked, qt_part = None, False, None
        for pp in path:
            if isinstance(pp, jax.tree_util.DictKey):
                key = str(pp.key)
                if key == "encoder":
                    stacked = True
                if key not in ("global", "groups", "shared_block", "encoder"):
                    name = key
            elif isinstance(pp, jax.tree_util.SequenceKey):
                stacked = True
            elif isinstance(pp, jax.tree_util.FlattenedIndexKey):
                qt_part = pp.key
        return name or "", stacked, qt_part

    def _pspec_for_leaf(self, path, shape) -> P:
        name, stacked, qt_part = self._path_info(path)
        logical = shape[1:] if stacked and len(shape) > 1 else shape
        if qt_part in (1, 2):
            # quant scale / zero-point: align the channel (last) dim with the
            # weight's column sharding when divisible, replicate the rest
            last = logical[-1] if logical else 1
            ps = [None] * len(logical)
            if logical and _div(last, self.tp):
                base = self.param_pspec(name, (1, last))
                if len(base) >= 2 and base[1] == "model":
                    ps[-1] = "model"
            pspec = P(*ps)
        else:
            pspec = self.param_pspec(name, logical)
        if self.fsdp and qt_part is None and len(logical) >= 2:
            # FSDP: additionally shard the largest replicated dim over the
            # DP axes (weights all-gathered per use, grads reduce-scattered)
            ps = list(pspec)
            while len(ps) < len(logical):
                ps.append(None)
            dpa = dp_axes(self.mesh)
            order = sorted(range(len(logical)), key=lambda i: -logical[i])
            for i in order:
                if ps[i] is None and _div(logical[i], self.dp):
                    ps[i] = dpa if len(dpa) > 1 else dpa[0]
                    break
            pspec = P(*ps)
        pspec = self._with_layer_dim(pspec, stacked and len(shape) > 1)
        if len(pspec) != len(shape):
            pspec = P(*([None] * len(shape)))
        return pspec

    def param_shardings(self, params: Any) -> Any:
        """NamedShardings matching the params pytree leaf-for-leaf (handles
        stacked scan groups and QuantizedTensor children)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        out = [NamedSharding(self.mesh, self._pspec_for_leaf(p, v.shape))
               for p, v in flat]
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- optimizer state: shard m/v like params + extra DP on the biggest dim
    def opt_pspec(self, name: str, shape: Tuple[int, ...], stacked: bool) -> P:
        logical = shape[1:] if stacked else shape
        base_ps = list(self.param_pspec(name, logical))
        while len(base_ps) < len(logical):
            base_ps.append(None)
        # ZeRO-ish: put "data" on the largest unsharded dim if divisible
        sizes = list(logical)
        order = sorted(range(len(sizes)), key=lambda i: -sizes[i])
        for i in order:
            if base_ps[i] is None and _div(sizes[i], self.dp_axis_size()):
                base_ps[i] = dp_axes(self.mesh) if len(dp_axes(self.mesh)) > 1 \
                    else dp_axes(self.mesh)[0]
                break
        ps = P(*base_ps)
        return self._with_layer_dim(ps, stacked)

    def dp_axis_size(self) -> int:
        return self.dp

    def opt_shardings(self, params: Any) -> Any:
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        out = []
        for path, v in flat:
            name, stacked, _ = self._path_info(path)
            ps = self.opt_pspec(name, v.shape, stacked and len(v.shape) > 1)
            if len(ps) != len(v.shape):
                ps = P(*([None] * len(v.shape)))
            out.append(NamedSharding(self.mesh, ps))
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- batches ------------------------------------------------------------
    def batch_pspec(self, batch_size: int) -> P:
        dp = dp_axes(self.mesh)
        total = self.dp
        if _div(batch_size, total):
            return P(dp if len(dp) > 1 else dp[0])
        return P()                                   # tiny batches replicate

    def batch_shardings(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        out = {}
        for k, v in batch.items():
            ps = self.batch_pspec(v.shape[0])
            nd = len(v.shape)
            out[k] = NamedSharding(self.mesh, P(*(list(ps) + [None] * (nd - len(ps)))))
        return out

    # -- KV / recurrent cache -----------------------------------------------
    def _paged_pool_fallback(self, name: str, shape: Tuple[int, ...],
                             kv: int) -> P:
        """Replicate a paged pool whose KV-head dim the model axis does
        not divide — a loud, non-fatal degradation: the engine still
        runs (and stays token-identical), it just gains no per-device
        capacity.  Crashing here would make whole architectures (odd
        GQA head counts) unservable on a given cluster size.  The
        divisibility is a property of (name, shape, tp), so warn ONCE
        per such triple ACROSS rules instances — tp sweeps build a
        fresh ``ShardingRules`` per engine, and a per-instance flag
        would re-emit the same warning for every point of the sweep."""
        key = (name, tuple(shape), self.tp)
        if key not in _PAGED_FALLBACK_WARNED:
            import warnings
            _PAGED_FALLBACK_WARNED.add(key)
            warnings.warn(
                f"paged KV pool {name!r} {shape}: num_kv_heads={kv} is not "
                f"divisible by the model-axis size {self.tp}; replicating "
                f"the pools (no tensor-parallel capacity win). Pick a "
                f"device count that divides the KV-head count to shard "
                f"them.", stacklevel=3)
        return P(*([None] * len(shape)))

    def cache_entry_pspec(self, name: str, shape: Tuple[int, ...]) -> P:
        """shape: per-layer cache entry, e.g. (B, S, KV, D) — or a PAGED
        pool: ``k_pages``/``v_pages`` (P, tok, KV, D) or lane-dense
        (P, KV, tok·D/128, 128) (``kernels.page_layout``) and lane-major
        ``k_scale``/``v_scale`` (P, KV, page) shard their KV-HEAD dim
        over "model" (pages are the serve path's capacity unit, so the
        pool partitions by head, never by page — block tables stay
        replicated host state and keep indexing the whole pool).  A
        KV-head count the axis does not divide falls back to
        replication with a warning instead of crashing."""
        sp, tp = self.spec, self.tp
        dp = dp_axes(self.mesh)
        dpa = dp if len(dp) > 1 else dp[0]
        if name in ("k_pages", "v_pages"):
            axis = page_layout.kv_axis(shape, sp.head_dim)
            KV = shape[axis]
            if tp <= 1:
                return P(None, None, None, None)
            if _div(KV, tp) and _div(sp.num_heads, tp):
                return P(*["model" if i == axis else None
                           for i in range(len(shape))])
            return self._paged_pool_fallback(name, shape, KV)
        if name in ("k_scale", "v_scale") and len(shape) == 3:
            KV = shape[1]
            if tp <= 1:
                return P(None, None, None)
            if _div(KV, tp) and _div(sp.num_heads, tp):
                return P(None, "model", None)
            return self._paged_pool_fallback(name, shape, KV)
        if name == "block_tables":
            return P(*([None] * len(shape)))     # replicated host state
        B = shape[0]
        batch_ax = dpa if _div(B, self.dp) else None
        if name in ("k", "v", "shared_k", "shared_v", "cross_k", "cross_v"):
            _, S, KV, D = shape
            if batch_ax is None and _div(S, self.dp):
                # long-context (batch too small for DP): shard the cache
                # sequence across the DP axes; softmax stats all-reduce is
                # inserted by GSPMD (distributed flash-decoding)
                return P(None, dpa, "model" if _div(KV, tp) else None, None)
            if _div(KV, tp) and self.cache_layout != "seq":
                return P(batch_ax, None, "model", None)
            # GQA with kv < tp: either shard head_dim (contraction ->
            # psum of full logits) or the sequence (softmax-stat
            # all-reduce only) — §Perf hillclimb knob, default seq
            if self.cache_layout == "headdim" and _div(D, tp):
                return P(batch_ax, None, None, "model")
            if _div(S, tp):
                return P(batch_ax, "model", None, None)
            if _div(D, tp):
                return P(batch_ax, None, None, "model")
            return P(batch_ax, None, None, None)
        if name == "ssm_state":                      # (B, nh, hd, st)
            nh = shape[1]
            return P(batch_ax, "model" if _div(nh, tp) else None, None, None)
        if name == "conv_state":
            return P(batch_ax, None, None)
        if name == "C":                              # mlstm (B, nh, dk, dv)
            return P(batch_ax, None, None, None)
        if len(shape) >= 1 and _div(shape[0], self.dp):
            return P(*([batch_ax] + [None] * (len(shape) - 1)))
        return P(*([None] * len(shape)))

    def cache_shardings(self, cache: Any) -> Any:
        """NamedShardings matching a cache pytree — contiguous decode
        caches AND paged serve caches (the latter carry ``block_tables``
        and per-slot ``pos``, both replicated; their pools go through
        the paged branch of ``cache_entry_pspec``)."""
        mesh = self.mesh
        out = {"pos": NamedSharding(mesh, P()), "groups": []}
        if "block_tables" in cache:
            out["block_tables"] = NamedSharding(mesh, P(None, None))
        for name in cache:
            if name not in out:       # per-slot conv state, expert counts
                out[name] = jax.tree_util.tree_map(
                    lambda _: NamedSharding(mesh, P()), cache[name])
        for g in cache["groups"]:
            layers = []
            for entry_dict in g:
                entry = {}
                for k, v in entry_dict.items():
                    ps = self.cache_entry_pspec(k, v.shape)
                    if len(ps) != len(v.shape):
                        ps = P(*([None] * len(v.shape)))
                    entry[k] = NamedSharding(mesh, ps)
                layers.append(entry)
            out["groups"].append(layers)
        return out

    def paged_pools_sharded(self, cache: Any) -> bool:
        """True iff a paged cache's pools actually shard over "model"
        (KV-head divisibility held) — the gate for running the paged
        attention per shard under ``shard_map``."""
        entry = next(e for g in cache["groups"] for e in g
                     if "k_pages" in e)
        ps = self.cache_entry_pspec("k_pages", entry["k_pages"].shape)
        return "model" in tuple(ps)
