"""Sharding rules: parameter/optimizer/activation/cache PartitionSpecs.

Conventions (see DESIGN.md §8):
* batch dims shard over ("pod","data") — pure DP across pods (grad all-reduce
  crosses the DCN once per step);
* weights shard over "model" (TP/EP) plus "data" (FSDP / ZeRO-3) on a large
  non-TP dim, replicated across "pod" so weight collectives stay on ICI;
* a dim is sharded over an axis only if divisible by the axis size — rules
  degrade to replication rather than producing invalid specs.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ArchConfig


def _axsize(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _fits(dim: int, mesh: Mesh, axis: str | None) -> bool:
    if axis is None:
        return True
    return axis in mesh.axis_names and dim % _axsize(mesh, axis) == 0


def _maybe(dim: int, mesh: Mesh, axis: str | None):
    return axis if axis is not None and _fits(dim, mesh, axis) and _axsize(mesh, axis) > 1 else None


def batch_spec(mesh: Mesh, shape: tuple[int, ...]) -> P:
    """Shard dim 0 over pod×data; drop axes that don't divide the batch."""
    ba: list[str] = []
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names and shape[0] % (n * _axsize(mesh, a)) == 0:
            ba.append(a)
            n *= _axsize(mesh, a)
    return P(tuple(ba) if ba else None, *([None] * (len(shape) - 1)))


# ---------------------------------------------------------------------------
# Parameter specs by tree path
# ---------------------------------------------------------------------------


def _param_spec(path: tuple[str, ...], shape: tuple[int, ...],
                mesh: Mesh) -> P:
    """Map one parameter (by its tree path + shape) to a PartitionSpec."""
    name = path[-1]
    inside_layers = "layers" in path
    fsdp = "data" if "data" in mesh.axis_names else None

    def spec(*axes):
        # validate divisibility dim-by-dim; drop the axis if it doesn't fit
        fixed = [_maybe(d, mesh, a) for d, a in zip(shape, axes)]
        return P(*fixed)

    # ---- top level ----
    if not inside_layers:
        if name == "embed":
            # vocab-parallel: the batch-sharded token gather partitions as a
            # masked local gather + one all-reduce over "model"; a d@data
            # shard would clash with the batch@data output and force XLA to
            # rematerialize the whole table on every device
            return spec("model", None)
        if name == "lm_head":
            return spec(fsdp, "model")
        if name == "adapter":
            return spec(None, fsdp)
        return P()                                  # final_norm etc.

    # strip the leading L (scan) dim for layer params
    def lspec(*axes):
        return spec(None, *axes)

    parent = path[-2] if len(path) >= 2 else ""
    grand = path[-3] if len(path) >= 3 else ""

    if name == "scale":                              # any RMSNorm
        return P()
    # ---- attention ----
    if parent == "attn" or grand == "attn":
        if name == "wq":
            return lspec(fsdp, "model", None)
        if name in ("wk", "wv"):
            # kv heads rarely divide the model axis; shard head_dim instead
            if _fits(shape[2], mesh, "model") and shape[2] >= _axsize(mesh, "model"):
                return lspec(fsdp, "model", None)
            return lspec(fsdp, None, "model")
        if name == "wo":
            return lspec("model", None, fsdp)
        if name in ("bq",):
            return lspec("model", None)
        if name in ("bk", "bv"):
            return lspec(None, "model") if not _fits(shape[1], mesh, "model") \
                else lspec("model", None)
        # MLA
        if name == "wq_a":
            return lspec(fsdp, None)
        if name == "wq_b":
            return lspec(None, "model", None)
        if name == "wkv_a":
            return lspec(fsdp, None)
        if name in ("wk_b", "wv_b"):
            return lspec(None, "model", None)
    # ---- mlp (incl. moe shared expert) ----
    if parent in ("mlp", "shared"):
        if name in ("wi", "wg"):
            return lspec(fsdp, "model")
        if name == "wo":
            return lspec("model", fsdp)
    # ---- moe ----
    if parent == "moe":
        if name == "router":
            return P()
        if name in ("w_in", "w_gate"):
            return lspec("model", fsdp, None)
        if name == "w_out":
            return lspec("model", None, fsdp)
    # ---- ssm ----
    if parent == "ssm":
        if name == "w_in":
            return lspec(fsdp, "model")
        if name == "conv_w":
            return lspec(None, "model")
        if name == "conv_b":
            return lspec("model")
        if name == "w_out":
            return lspec("model", fsdp)
        if name in ("A_log", "D", "dt_bias"):
            return P()
    return P()


def _path_names(path) -> tuple[str, ...]:
    out = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            out.append(str(p.key))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            out.append(p.name)
        else:
            out.append(str(p))
    return tuple(out)


def param_shardings(params_shape: Any, mesh: Mesh) -> Any:
    """NamedSharding pytree matching a params (or ShapeDtypeStruct) pytree.

    Also covers optimizer-state trees: full-shape moments ("m"/"v" subtrees)
    reuse the parameter rules via their path tail; Adafactor's factored
    moments ("vr"/"vc", one dim removed) inherit the parent spec minus the
    removed dim.
    """
    flat, tree = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = []
    for path, leaf in flat:
        names = _path_names(path)
        shape = tuple(leaf.shape)
        if names[-1] == "vr":          # parent shape minus last dim
            parent = _param_spec(names[:-1], shape + (1,), mesh)
            spec = P(*(tuple(parent) + (None,) * (len(shape) - len(parent)))[
                :len(shape)])
        elif names[-1] == "vc":        # parent shape minus dim -2
            parent = _param_spec(names[:-1],
                                 shape[:-1] + (1,) + shape[-1:], mesh)
            pl = tuple(parent) + (None,) * (len(shape) + 1 - len(parent))
            spec = P(*(pl[:len(shape) - 1] + (pl[len(shape)],)))
        else:
            spec = _param_spec(names, shape, mesh)
        # drop axes that don't divide (factored shapes can break divisibility)
        fixed = []
        padded = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
        for i, a in enumerate(padded[:len(shape)]):
            if a is None:
                fixed.append(None)
                continue
            axes = a if isinstance(a, tuple) else (a,)
            n = math.prod(_axsize(mesh, ax) for ax in axes)
            fixed.append(a if n > 0 and shape[i] % n == 0 else None)
        specs.append(NamedSharding(mesh, P(*fixed)))
    return jax.tree_util.tree_unflatten(tree, specs)


# ---------------------------------------------------------------------------
# Cache specs (decode)
# ---------------------------------------------------------------------------


def cache_shardings(cache_shape: Any, mesh: Mesh, *,
                    seq_shard: bool = False) -> Any:
    """Decode-cache specs.

    ``seq_shard=True`` shards the cache *sequence* dim over "model"
    (flash-decode style): the per-step attention becomes partial-softmax +
    tiny psum combine, instead of all-gathering head-dim-sharded K/V — the
    §Perf fix for collective-bound decode cells.
    """
    def _ba(dim: int):
        out, n = [], 1
        for a in ("pod", "data"):
            if a in mesh.axis_names and dim % (n * _axsize(mesh, a)) == 0:
                out.append(a)
                n *= _axsize(mesh, a)
        return tuple(out) if out else None

    def one(path, leaf):
        name = _path_names(path)[-1]
        shp = tuple(leaf.shape)
        ba = _ba(shp[1]) if len(shp) > 1 else None
        if name == "pos":
            return NamedSharding(mesh, P())
        if name in ("k", "v"):           # (L, B, S, KH, hd)
            if seq_shard and _fits(shp[2], mesh, "model"):
                return NamedSharding(mesh, P(None, ba, "model", None, None))
            kh_ok = _fits(shp[3], mesh, "model") and shp[3] >= _axsize(mesh, "model")
            spec = (P(None, ba, None, "model", None) if kh_ok
                    else P(None, ba, None, None, _maybe(shp[4], mesh, "model")))
            return NamedSharding(mesh, spec)
        if name in ("ckv", "krope"):     # (L, B, S, r)
            if seq_shard and _fits(shp[2], mesh, "model"):
                return NamedSharding(mesh, P(None, ba, "model", None))
            return NamedSharding(
                mesh, P(None, ba, None, _maybe(shp[3], mesh, "model")))
        if name == "state":              # (L, B, nh, hp, ds)
            if _fits(shp[2], mesh, "model"):
                return NamedSharding(mesh, P(None, ba, "model", None, None))
            return NamedSharding(
                mesh, P(None, ba, None, _maybe(shp[3], mesh, "model"), None))
        if name == "conv":               # (L, B, K-1, conv_dim)
            return NamedSharding(
                mesh, P(None, ba, None, _maybe(shp[3], mesh, "model")))
        return NamedSharding(mesh, P())

    flat, tree = jax.tree_util.tree_flatten_with_path(cache_shape)
    return jax.tree_util.tree_unflatten(
        tree, [one(p, l) for p, l in flat])


def batch_shardings(batch_shape: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda l: NamedSharding(mesh, batch_spec(mesh, tuple(l.shape))),
        batch_shape)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
