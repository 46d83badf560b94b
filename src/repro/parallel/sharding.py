"""Logical-axis sharding: DP / FSDP(ZeRO-3) / TP / EP / SP rules.

Models call `constrain(x, *logical_axes)`; the launcher activates a mesh +
rule set before tracing.  With no activation (unit tests, single CPU) every
constraint is a no-op, so model code never depends on a mesh.

Rule sets map logical axis names → physical mesh axes:

  batch    : data-parallel batch dim            → ("pod", "data")
  fsdp     : ZeRO-3 parameter shard dim         → ("pod", "data")
  tp       : tensor-parallel (heads/ff/vocab)   → "model"
  act_seq  : sequence-parallel residual stream  → "model"
  kv_feat  : decode KV-cache feature shard      → "model"

Parameter placement is name-based: `param_specs(params)` walks the pytree
and assigns (fsdp, tp) on the (in, out) dims of column-parallel weights and
(tp, fsdp) on row-parallel ones, experts on (tp→EP, fsdp, ·), everything
else replicated.  Stacked layer params get a leading None for the period
axis.
"""
from __future__ import annotations

import dataclasses
import re
import warnings
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass
class ShardingCtx:
    mesh: Mesh
    rules: dict

    def resolve(self, *logical) -> P:
        axes = []
        for name in logical:
            if name is None:
                axes.append(None)
                continue
            phys = self.rules.get(name)
            axes.append(phys)
        return P(*axes)

    def phys_axis(self, logical: str) -> str | None:
        """The single physical mesh axis `logical` maps to, or None when the
        rule is absent, multi-axis, or names an axis this mesh doesn't have
        (callers use this to decide whether a collective path can run)."""
        phys = self.rules.get(logical)
        if isinstance(phys, str) and phys in self.mesh.axis_names:
            return phys
        return None


_ACTIVE: ShardingCtx | None = None


def default_rules(multi_pod: bool) -> dict:
    dp = ("pod", "data") if multi_pod else ("data",)
    return {"batch": dp, "fsdp": dp, "tp": "model",
            "act_seq": "model", "kv_feat": "model", "expert": "model"}


def serve_rules(multi_pod: bool) -> dict:
    """Inference sharding profile (§Perf iteration): weights tensor-parallel
    over `model` only, replicated across the DP axes — no per-token FSDP
    all-gathers; batch/caches still split over DP."""
    r = default_rules(multi_pod)
    r["fsdp"] = None
    return r


_AXIS_ORDER = ("pod", "data", "model")


def build_mesh(spec: str | int | None = None, *, pod: int | None = None,
               data: int | None = None, model: int | None = None,
               devices=None) -> Mesh:
    """2-D/3-D mesh builder over (pod ×) data × model, rule-driven like the
    logical-axis rules above: the axis *names* are what `default_rules` /
    `serve_rules` map onto, so any mesh built here composes with
    `activate()` (a `pod` axis switches on the multi-pod rule set).

    Accepted specs (string forms are what `--shard` forwards):
      build_mesh(4)  / build_mesh("4")       → data-filled × 4-way model
      build_mesh("2x4") / build_mesh("2x2x2")→ (data, model) / (pod, data,
                                               model) shapes
      build_mesh("data=2,model=4")           → named axes, any subset
      build_mesh(model=4)                    → keyword form of the same

    An omitted `data` is filled with the remaining devices; `pod` appears
    only when requested, keeping 2-D meshes 2-D.
    """
    if spec is not None:
        if pod is not None or data is not None or model is not None:
            raise ValueError("pass a spec or keyword axes, not both")
        named = {}
        s = str(spec).strip()
        if "=" in s:
            for part in s.split(","):
                name, _, val = part.partition("=")
                if name.strip() not in _AXIS_ORDER:
                    raise ValueError(f"unknown mesh axis {name.strip()!r} "
                                     f"(expected {_AXIS_ORDER})")
                named[name.strip()] = int(val)
        elif "x" in s:
            dims = [int(v) for v in s.split("x")]
            if len(dims) not in (2, 3):
                raise ValueError(f"mesh spec {s!r} must be 2-D or 3-D")
            named = dict(zip(_AXIS_ORDER[-len(dims):], dims))
        else:
            named = {"model": int(s)}
        pod, data, model = (named.get(a) for a in _AXIS_ORDER)

    for name, val in zip(_AXIS_ORDER, (pod, data, model)):
        if val is not None and val < 1:
            raise ValueError(f"mesh axis {name}={val} must be >= 1 "
                             f"(omit the axis to disable it)")
    data_explicit = data is not None
    devs = np.asarray(devices if devices is not None else jax.devices())
    model = model or 1
    fixed = (pod or 1) * model
    if data is None:
        if devs.size % fixed:
            raise ValueError(f"{devs.size} devices not divisible by "
                             f"pod*model={fixed}")
        data = devs.size // fixed
    shape = tuple(v for v in (pod, data, model) if v is not None)
    axes = tuple(a for a, v in zip(_AXIS_ORDER, (pod, data, model))
                 if v is not None)
    need = int(np.prod(shape))
    if need > devs.size:
        raise ValueError(f"mesh {dict(zip(axes, shape))} needs {need} "
                         f"devices, only {devs.size} available")
    if need < devs.size and data_explicit:
        # a fully-explicit spec that underfills is usually a typo'd
        # throughput loss, not intent — flag it (an inferred data axis
        # always fills, so this only fires on explicit specs)
        warnings.warn(f"mesh {dict(zip(axes, shape))} uses {need} of "
                      f"{devs.size} devices", stacklevel=2)
    return Mesh(devs[:need].reshape(shape), axes)


def activate(mesh: Mesh, rules: dict | None = None) -> ShardingCtx:
    """Make `mesh` + `rules` the process-wide sharding context.  The rules
    act through `with_sharding_constraint`, which takes Auto mesh axes
    only, so a mesh with Explicit axes (`jax.make_mesh`'s default) is
    rebuilt over the same devices with Auto axes."""
    global _ACTIVE
    mesh = Mesh(mesh.devices, mesh.axis_names)
    multi_pod = "pod" in mesh.axis_names
    _ACTIVE = ShardingCtx(mesh, rules or default_rules(multi_pod))
    return _ACTIVE


def deactivate() -> None:
    global _ACTIVE
    _ACTIVE = None


def active() -> ShardingCtx | None:
    return _ACTIVE


def constrain(x: jax.Array, *logical) -> jax.Array:
    """with_sharding_constraint by logical axis names (no-op when inactive).

    Axes whose size doesn't divide the assigned mesh axes are silently
    dropped to None (e.g. 8 KV heads on a 16-way model axis)."""
    ctx = _ACTIVE
    if ctx is None:
        return x
    spec = ctx.resolve(*logical)
    fixed = []
    for dim, ax in zip(x.shape, spec):
        if ax is None:
            fixed.append(None)
            continue
        size = 1
        for a in ((ax,) if isinstance(ax, str) else ax):
            size *= ctx.mesh.shape[a]
        fixed.append(ax if dim % size == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(*fixed)))


# ---------------------------------------------------------------------------
# Parameter partition specs (name-based rules)
# ---------------------------------------------------------------------------

# (regex on the dot-joined path) -> logical axes per trailing dims.
# Matching is last-rule-wins; dims are right-aligned (leading stack/period
# axes get None automatically).
_PARAM_RULES: list[tuple[str, tuple]] = [
    (r"embedding$",              ("tp", "fsdp")),
    (r"unembed$",                ("fsdp", "tp")),
    (r"\b(wq|wk|wv)$",           ("fsdp", "tp")),
    (r"\bwo$",                   ("tp", "fsdp")),
    (r"\b(w_gate|w_up)$",        ("fsdp", "tp")),
    (r"\bw_down$",               ("tp", "fsdp")),
    (r"\brouter$",               (None, None)),
    # MoE experts (E, d, f) / (E, f, d): EP on experts + FSDP on d
    (r"moe.*\b(w_gate|w_up)$",   ("expert", "fsdp", None)),
    (r"moe.*\bw_down$",          ("expert", None, "fsdp")),
    # MLA
    (r"\bw_dq$",                 ("fsdp", None)),
    (r"\bw_uq$",                 (None, "tp")),
    (r"\bw_dkv$",                ("fsdp", None)),
    (r"\bw_kr$",                 (None, None)),
    (r"\b(w_uk|w_uv)$",          (None, "tp")),
    # mamba
    (r"\bw_in$",                 ("fsdp", "tp")),
    (r"\bconv_w$",               (None, "tp")),
    (r"\b(conv_b|d_skip|dt_bias)$", ("tp",)),
    (r"\ba_log$",                ("tp", None)),
    (r"\bw_bc$",                 ("tp", None)),
    (r"\bw_dt_down$",            ("tp", None)),
    (r"\bw_dt_up$",              (None, "tp")),
    # xlstm
    (r"\bw_if$",                 (None, None)),
    (r"\b(w_gates|r_gates)$",    ("fsdp", "tp")),
]

# Serving KV caches: attention K/V rows (and their int8 row scales) split
# over KV heads on the tp axis, where the wk/wv columns that produce them
# already are; every other cache leaf stays replicated.
CACHE_RULES: list[tuple[str, tuple]] = [
    (r"\b(k|v)$",                ("tp", None)),
    (r"\b(ks|vs)$",              ("tp",)),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return ".".join(parts)


def spec_for(path_str: str, ndim: int, rules=None) -> tuple:
    # QuantizedTensor leaves: the .values/.scale arrays inherit the parent
    # weight's rule (right-aligned; non-divisible dims drop to None later).
    path_str = re.sub(r"\.(values|scale)$", "", path_str)
    chosen = None
    for pattern, axes in (_PARAM_RULES if rules is None else rules):
        if re.search(pattern, path_str):
            chosen = axes
    if chosen is None:
        return (None,) * ndim
    pad = ndim - len(chosen)
    if pad < 0:        # param has fewer dims than rule (shouldn't happen)
        return (None,) * ndim
    return (None,) * pad + tuple(chosen)


def param_specs(params: Any, rules=None) -> Any:
    """Logical spec pytree mirroring `params`."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec_for(_path_str(path), leaf.ndim, rules),
        params)


def param_shardings(params: Any, ctx: ShardingCtx | None = None,
                    rules=None) -> Any:
    """NamedSharding pytree for jit in_shardings (divisibility-checked);
    `rules` defaults to the parameter table (`CACHE_RULES` places KV
    caches)."""
    ctx = ctx or _ACTIVE
    specs = param_specs(params, rules)

    def to_sharding(leaf, logical):
        fixed = []
        for dim, name in zip(leaf.shape, logical):
            if name is None:
                fixed.append(None)
                continue
            ax = ctx.rules.get(name)
            if ax is None:
                fixed.append(None)
                continue
            size = 1
            for a in ((ax,) if isinstance(ax, str) else ax):
                size *= ctx.mesh.shape[a]
            fixed.append(ax if dim % size == 0 else None)
        return NamedSharding(ctx.mesh, P(*fixed))

    return jax.tree_util.tree_map(to_sharding, params, specs)
