"""Parameter/activation sharding rules (GSPMD partition specs).

Mesh axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")``
multi-pod.  Default layout (the paper-faithful baseline the solver then
perturbs):

  * batch over (pod, data) — pure DP across pods (the pod axis role is a
    solver decision, DESIGN.md: SLR-assignment analogue);
  * weights 2D-sharded: contraction dim over ``data`` (ZeRO/FSDP-style so
    fp32 master + Adam state fit HBM), output-feature / head / expert /
    vocab dim over ``model`` (tensor parallel);
  * anything non-divisible falls back to replication **per dim** — this
    fixup is what makes kv_heads < model-size (yi-34b, qwen3-moe) and
    n_experts < model-size (mixtral) legal without special cases; head
    padding (padding-for-computation) keeps the big dims divisible.

Specs are assigned by parameter *name* via path matching and apply equally
to optimizer-state mirrors.  Scanned layer stacks get a leading None.
"""
from __future__ import annotations

import re
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


# name -> spec template (checked/fixed against shapes at assignment)
_RULES: list[tuple[str, tuple]] = [
    (r"embed$", ("model", "data")),
    (r"lm_head$", ("data", "model")),
    (r"\bwq$|\bwk$|\bwv$", ("data", "model")),
    (r"\bwo$", ("model", "data")),
    (r"\bw1$|\bw3$", ("data", "model")),          # 2d mlp (3d moe handled below)
    (r"\bw2$", ("model", "data")),
    (r"router$", ("data", None)),
    (r"conv_w$", (None, "model")),
    (r"w_gate$|w_in$|w_a$|w_x$", ("data", "model")),
    (r"w_out$", ("model", "data")),
    (r"\bwr$|\bwg$|cm_r$|cm_k$", ("data", "model")),
    (r"cm_v$", ("model", "data")),
    (r"wd1$", ("data", None)),
    (r"wd2$", (None, "model")),
    (r"\bbq$|\bbk$|\bbv$", ("model",)),
]

_MOE_RULES = {
    # (param, experts divisible): spec
    ("w1", True): ("model", "data", None),
    ("w3", True): ("model", "data", None),
    ("w2", True): ("model", None, "data"),
    ("w1", False): (None, "data", "model"),
    ("w3", False): (None, "data", "model"),
    ("w2", False): (None, "model", "data"),
}


def _fixup(mesh: Mesh, spec: tuple, shape: tuple[int, ...]) -> P:
    """Drop axes that do not divide their dim (per-dim replication)."""
    spec = tuple(spec) + (None,) * (len(shape) - len(spec))
    fixed = []
    for axes, dim in zip(spec, shape):
        if axes is None:
            fixed.append(None)
        elif dim % axis_size(mesh, axes) == 0:
            fixed.append(axes)
        else:
            fixed.append(None)
    return P(*fixed)


def param_spec(mesh: Mesh, path: str, shape: tuple[int, ...]) -> P:
    """Partition spec for one parameter identified by its tree path."""
    scanned = bool(re.search(r"\blayers\b", path))
    base_shape = shape[1:] if scanned else shape
    name = path.split("/")[-1]
    spec: tuple | None = None
    if re.search(r"w[123]$", name) and len(base_shape) == 3:
        div = base_shape[0] % axis_size(mesh, "model") == 0 \
            if "model" in mesh.axis_names else False
        spec = _MOE_RULES[(name, div)]
    else:
        for pat, sp in _RULES:
            if re.search(pat, name):
                spec = sp
                break
    if spec is None:
        spec = (None,) * len(base_shape)      # norms, gates, scalars
    p = _fixup(mesh, spec, base_shape)
    if scanned:
        p = P(None, *p)
    return p


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def shard_params(mesh: Mesh, params: Any) -> Any:
    """NamedSharding pytree matching ``params`` (works for opt-state mirrors
    via tree structure reuse)."""
    def spec_of(path, leaf):
        return NamedSharding(mesh, param_spec(mesh, _path_str(path),
                                              leaf.shape))
    return jax.tree_util.tree_map_with_path(spec_of, params)


def batch_spec(mesh: Mesh, global_batch: int) -> P:
    axes = dp_axes(mesh)
    if axes and global_batch % axis_size(mesh, axes) == 0:
        return P(axes)
    # try data only, then replicate (long_500k batch=1)
    if "data" in mesh.axis_names and global_batch % mesh.shape["data"] == 0:
        return P("data")
    return P(None)


#: Activation layouts: the batch over the data axes, heads over ``model``.
#: Under FSDP x TP each device then holds its own rows of every activation
#: and gathers the weights it contracts with.
RESIDUAL = ("batch", None, None)              # (B, S, D)
HEADS = ("batch", None, "model", None)        # (B, S, H, hd)


def constrain(x: jax.Array, layout: tuple) -> jax.Array:
    """``x`` laid out as ``layout`` when traced under a mesh of ``Auto``
    axes (``jax.sharding.use_abstract_mesh``); unchanged outside one, so
    single-device programs compile as if it were not there (and under
    ``Explicit`` axes, where shardings are types, not hints).  An axis
    that does not divide its dimension is dropped (replicated)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not mesh.are_all_axes_auto:
        return x
    first = tuple(batch_spec(mesh, x.shape[0]))[0] \
        if layout[0] == "batch" else layout[0]
    spec = _fixup(mesh, (first,) + tuple(layout[1:]), x.shape)
    return jax.lax.with_sharding_constraint(x, spec)


def tokens_sharding(mesh: Mesh, global_batch: int,
                    extra_dims: int = 1) -> NamedSharding:
    spec = batch_spec(mesh, global_batch)
    return NamedSharding(mesh, P(*(tuple(spec) + (None,) * extra_dims)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
