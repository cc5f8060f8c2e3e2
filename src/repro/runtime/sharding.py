"""Logical-axis -> mesh-axis sharding rules (GSPMD/pjit).

Mesh axes: ``("data", "model")`` per pod, ``("pod", "data", "model")``
multi-pod (launch/mesh.py).  FSDP axes = ("pod", "data") when present.

Parameter rules (train & serve — serve reuses the FSDP layout and
all-gathers weights per layer; the EP-heavy serving alternative is a §Perf
experiment):

  embeddings / lm head     (V, d)        -> (model, fsdp)
  attn q/k/v projections   (d, H*hd)     -> (fsdp, model)   column parallel
  attn output projection   (H*hd, d)     -> (model, fsdp)   row parallel
  MLA down-projections     (d, r)        -> (fsdp, None)
  MLA up-projections       (r, H*x)      -> (None, model)
  mlp gate/up              (d, ff)       -> (fsdp, model)
  mlp down                 (ff, d)       -> (model, fsdp)
  MoE expert stacks        (E, d, ff)    -> (model, fsdp, None)   EP
                           (E, ff, d)    -> (model, None, fsdp)
  MoE router               (d, E)        -> (fsdp, None)
  mamba in_proj            (d, 2i+2GS+H) -> (fsdp, model)
  mamba out_proj           (i, d)        -> (model, fsdp)
  mamba conv/gate/A/dt/D   channel dim   -> (model)
  norms                    (d,)          -> replicated

Stacked (scanned) parameters carry 1-2 leading layer dims -> padded with
None.  Activations/batch: batch dim over (pod, data); KV caches: batch over
(pod, data), heads over model; ssm state heads over model."""
from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def fsdp_axes(axis_names: Sequence[str]):
    ax = tuple(a for a in ("pod", "data") if a in axis_names)
    if len(ax) == 1:
        return ax[0]
    return ax if ax else None


def dp_axes(axis_names: Sequence[str]):
    return fsdp_axes(axis_names)


_RULES = [
    # (path substrings (all must match), trailing spec builder)
    (("embed", "table"), lambda f: ("model", f)),
    (("out", "table"), lambda f: ("model", f)),
    (("wq_down",), lambda f: (f, None)),
    (("wkv_down",), lambda f: (f, None)),
    (("wq_up",), lambda f: (None, "model")),
    (("wkv_up",), lambda f: (None, "model")),
    (("attn", "wq"), lambda f: (f, "model")),
    (("attn", "wk"), lambda f: (f, "model")),
    (("attn", "wv"), lambda f: (f, "model")),
    (("attn", "wo"), lambda f: ("model", f)),
    (("moe", "shared", "w_gate"), lambda f: (f, "model")),
    (("moe", "shared", "w_up"), lambda f: (f, "model")),
    (("moe", "shared", "w_down"), lambda f: ("model", f)),
    (("moe", "router"), lambda f: (f, None)),
    (("moe", "w_gate"), lambda f: ("model", f, None)),
    (("moe", "w_up"), lambda f: ("model", f, None)),
    (("moe", "w_down"), lambda f: ("model", None, f)),
    (("w_gate",), lambda f: (f, "model")),
    (("w_up",), lambda f: (f, "model")),
    (("w_down",), lambda f: ("model", f)),
    (("in_proj",), lambda f: (f, "model")),
    (("out_proj",), lambda f: ("model", f)),
    (("conv_w",), lambda f: (None, "model")),
    (("conv_b",), lambda f: ("model",)),
    (("gate_norm",), lambda f: ("model",)),
    (("mixer", "A_log"), lambda f: ("model",)),
    (("mixer", "dt_bias"), lambda f: ("model",)),
    (("mixer", "D"), lambda f: ("model",)),
]


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _axis_size(axes, sizes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return sizes.get(axes, 1)
    n = 1
    for a in axes:
        n *= sizes.get(a, 1)
    return n


def _divisibility_guard(spec, shape, sizes):
    """GSPMD requires every sharded dim to divide evenly by its axis
    product; drop (replicate) the axes of any dim that does not (odd
    vocabularies, small head counts — see EXPERIMENTS.md §Dry-run notes)."""
    fixed = []
    for dim, axes in zip(shape, tuple(spec) + (None,) * (len(shape)
                                                         - len(tuple(spec)))):
        n = _axis_size(axes, sizes)
        fixed.append(axes if (n > 0 and dim % n == 0) else None)
    return P(*fixed)


_HEAD_DIM_RULES = {
    # attn weights whose model-sharded dim is a (heads*hd) dim: position of
    # that dim in the trailing spec (-1 = last/out, -2 = first/in)
    ("attn", "wq"): -1, ("attn", "wk"): -1, ("attn", "wv"): -1,
    ("attn", "wo"): -2,
}


def _head_granularity_guard(spec, shape, sizes, head_dim, pos):
    """Sharding a (heads*hd) dim must land on whole heads: if
    (dim/hd) % model != 0, GSPMD would split inside heads and reshard the
    (B,S,H,hd) activations every layer (§Perf finding, EXPERIMENTS.md
    tinyllama iteration 3).  Replicate that dim instead."""
    if head_dim is None:
        return spec
    inner = list(spec)
    idx = len(shape) + pos if pos < 0 else pos
    axes = inner[idx]
    n = _axis_size(axes, sizes)
    heads = shape[idx] // max(head_dim, 1)
    if n > 1 and (shape[idx] % head_dim or heads % n):
        inner[idx] = None
    return P(*inner)


def _leaf_pspec(path, leaf, axis_names, sizes, head_dim=None) -> P:
    ps = _path_str(path)
    f = fsdp_axes(axis_names)
    ndim = len(leaf.shape)
    for keys, rule in _RULES:
        if all(k in ps for k in keys):
            trailing = rule(f)
            if len(trailing) > ndim:     # tiny smoke tensors
                trailing = trailing[-ndim:]
            pad = (None,) * (ndim - len(trailing))
            spec = _divisibility_guard(P(*(pad + tuple(trailing))),
                                       leaf.shape, sizes)
            for hkeys, pos in _HEAD_DIM_RULES.items():
                if all(k in ps for k in hkeys) and "wq_" not in ps \
                        and "wkv_" not in ps:
                    spec = _head_granularity_guard(spec, leaf.shape, sizes,
                                                   head_dim, pos)
                    break
            return spec
    return P()                            # replicate (norms, scalars)


def param_pspecs(spec_tree: Any, axis_names: Sequence[str],
                 axis_sizes: dict | None = None, head_dim: int | None = None):
    """PartitionSpec tree congruent with a params (or ShapeDtypeStruct)
    tree.  ``axis_sizes`` ({axis: size}) enables the divisibility guard;
    ``head_dim`` the head-granularity guard for attention weights."""
    sizes = axis_sizes or {}
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _leaf_pspec(path, leaf, axis_names, sizes,
                                       head_dim),
        spec_tree)


def opt_state_pspecs(opt_specs: Any, p_pspecs: Any):
    """Optimizer state: moments inherit the parameter spec; count
    replicated."""
    mu = jax.tree_util.tree_map(
        lambda spec: {"m": spec, "v": spec}, p_pspecs,
        is_leaf=lambda x: isinstance(x, P))
    return {"mu": mu, "count": P()}


_BATCH_RULES = {
    "tokens": lambda d: P(d, None),
    "labels": lambda d: P(d, None),
    "token": lambda d: P(d, None),
    "frames": lambda d: P(d, None, None),
    "prefix": lambda d: P(d, None, None),
    "cache_index": lambda d: P(),
}

_CACHE_RULES = {
    # leading layer-stack dims padded by _pad below
    "k": lambda d: P(d, None, "model", None),
    "v": lambda d: P(d, None, "model", None),
    "ckv": lambda d: P(d, None, None),
    "k_rope": lambda d: P(d, None, None),
    "ssm": lambda d: P(d, "model", None, None),
    "cx": lambda d: P(d, None, "model"),
    "cb": lambda d: P(d, None, "model"),
    "cc": lambda d: P(d, None, "model"),
    "memory": lambda d: P(d, None, None),
}


def _pad(spec: P, ndim: int) -> P:
    inner = tuple(spec)
    if len(inner) > ndim:
        inner = inner[-ndim:]
    return P(*(((None,) * (ndim - len(inner))) + inner))


def input_pspecs(input_specs: Any, axis_names: Sequence[str],
                 axis_sizes: dict | None = None):
    """PartitionSpecs for a step's input tree (train batch or decode
    state)."""
    d = dp_axes(axis_names)
    sizes = axis_sizes or {}

    def leaf(path, x):
        ps = _path_str(path)
        name = ps.split("/")[-1]
        ndim = len(x.shape)
        spec = None
        if "caches" in ps or name in _CACHE_RULES:
            rule = _CACHE_RULES.get(name)
            if rule is not None:
                spec = _pad(rule(d), ndim)
        if spec is None and name in _BATCH_RULES:
            spec = _pad(_BATCH_RULES[name](d), ndim)
        if spec is None:
            return P()
        return _divisibility_guard(spec, x.shape, sizes)

    return jax.tree_util.tree_map_with_path(leaf, input_specs)


def guard_pspec(spec: P, shape, mesh: Mesh) -> P:
    """Public divisibility guard for hand-built specs (e.g. logits)."""
    return _divisibility_guard(_pad(spec, len(shape)), shape,
                               dict(mesh.shape))


def to_shardings(pspec_tree: Any, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), pspec_tree,
        is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------- bound exchange
def _round_down_f32(values):
    """float32 narrowing that never rounds UP: theta_lb is a certified
    lower bound, and nearest-rounding a float64 bound up by half an ulp
    would let the exchange prune a boundary candidate unsoundly.  One ulp
    of looseness only ever keeps an extra candidate alive."""
    v64 = np.asarray(values, np.float64)
    v32 = v64.astype(np.float32)
    return np.where(v32.astype(np.float64) > v64,
                    np.nextafter(v32, np.float32(-np.inf)), v32)


@functools.lru_cache(maxsize=None)
def _amax_fn(mesh: Mesh, present: tuple):
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(),
                       out_specs=P())
    def _amax(v):
        for a in present:
            v = jax.lax.pmax(v, a)
        return v

    return jax.jit(_amax)


def all_reduce_max(values, mesh: Mesh, axes: Sequence[str] = ("pod", "data")):
    """All-reduce-max of a replicated bound vector over the repository
    shard axes (DESIGN.md §5).

    The partition scheduler's theta_lb exchange, called at each exchange
    point (between waves, after verification rounds): every shard
    contributes its per-query lower bounds and receives the global max,
    so a bound raised anywhere prunes candidates everywhere.  ``values``
    is a (B,) array (one slot per in-flight query), replicated across the
    mesh; axes absent from the mesh are skipped, so the same call works
    on the production (pod, data, model) mesh, the single-pod (data,
    model) mesh, and the single-device smoke mesh.  The jitted shard_map
    is cached per (mesh, axes) — this runs once per verification round.
    Returns a host ndarray (float32, rounded toward -inf so the bound
    stays certified).
    """
    vals = _round_down_f32(values)
    present = tuple(a for a in axes if a in mesh.axis_names)
    if not present:
        return vals
    return np.asarray(_amax_fn(mesh, present)(jax.numpy.asarray(vals)))


def bound_exchange_for(mesh: Mesh, axes: Sequence[str] = ("pod", "data")):
    """A scheduler ``bound_exchange`` hook closing over ``mesh``."""
    return lambda theta: all_reduce_max(theta, mesh, axes)
