"""Sharding rules: DP / FSDP / TP / EP / SP over the production mesh.

Mesh axes (launch/mesh.py): single-pod ``("data", "model")`` = (16, 16);
multi-pod ``("pod", "data", "model")`` = (2, 16, 16).  ``"pod"`` extends
the data axis (gradient sync crosses pods; TP stays intra-pod — ICI-aware
placement).  An optional ``"expert"`` axis (carved out of the data axis,
``make_production_mesh(expert=S)``) enables expert-parallel all-to-all
MoE dispatch (repro.ep): expert weights shard E over it and tokens are
exchanged between expert shards intra-pod.

Param rules (per tensor-role, applied by pytree path):

* embeddings/lm_head: vocab → model, d_model → fsdp axes
* attention qkv: d_model(in) → fsdp, heads(out) → model (Megatron TP)
* attention out: heads(in) → model, d_model(out) → fsdp
* mlp w1/w3: d → fsdp, ff → model;  w2: ff → model, d → fsdp
* MoE experts: E → "expert" when the mesh carves a dedicated expert
  axis that divides E (repro.ep all-to-all dispatch); else E → model
  when E % model_size == 0 (expert parallelism on the TP axis), else
  ff → model (TP inside experts)
* mamba: d_inner → model (heads-analog), d_model → fsdp
* norms/scalars: replicated
* stacked layer dim (leading L): never sharded

Sync-policy variants (train/train_step.py):
* "unopt"/"lc" — pure DP: params replicated over (pod, data) (no fsdp dim)
* "afe"/"afe_bucket" — FSDP: params sharded over (pod, data) as above

The model code calls :func:`shard` on activations; it is a no-op unless a
mesh context is installed (smoke tests run un-meshed on one device).
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "repro_mesh", default=None)

#: The one copy of the expert-parallel mesh-axis name (repro.ep and
#: launch.mesh import it; a drifting literal would silently disable the
#: EP dispatch path).
EXPERT_AXIS = "expert"


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh]):
    token = _MESH_CTX.set(mesh)
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _MESH_CTX.reset(token)


def current_mesh() -> Optional[Mesh]:
    return _MESH_CTX.get()


def fsdp_axes(mesh: Optional[Mesh] = None):
    """The data-parallel axes tuple: ("pod","data") or ("data",)."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return ("data",)
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def expert_axis_size(mesh: Optional[Mesh] = None) -> int:
    """Size of the dedicated expert-parallel axis (0 when the mesh does
    not carve one — the single-host MoE dispatch path)."""
    mesh = mesh or current_mesh()
    if mesh is None or EXPERT_AXIS not in mesh.axis_names:
        return 0
    return dict(mesh.shape)[EXPERT_AXIS]


def _model_size(mesh: Mesh) -> int:
    """TP axis size; 1 for meshes without a "model" axis (e.g. an
    expert-only EP test mesh)."""
    return dict(mesh.shape).get("model", 1)


def shard(x, *spec):
    """with_sharding_constraint that degrades to identity without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def batch_spec() -> P:
    return P(fsdp_axes(), None)


def act_spec() -> P:
    """(B, S, D) activations: batch over data axes, D unsharded between
    layers (TP collectives happen inside the layer einsums)."""
    return P(fsdp_axes(), None, None)


def shard_act(x):
    """Megatron-style sequence-parallel activation constraint.

    Residual-stream activations between layers are sharded over the model
    axis along the *sequence* dimension whenever it divides — this is what
    bounds the remat-saved layer inputs (L × tokens × d_model bf16 would
    otherwise dominate HBM: qwen2.5-32b train_4k saves 42 GB/device
    un-sharded, 2.6 GB with SP — EXPERIMENTS.md §Perf iteration 1).
    Falls back to batch-only sharding for ragged lengths (whisper's 1500
    frames) and decode (S=1).
    """
    mesh = current_mesh()
    if mesh is None or x.ndim != 3:
        return x
    fa = fsdp_axes(mesh)
    msize = _model_size(mesh)
    dsize = 1
    for a in fa:
        dsize *= mesh.shape[a]
    b_ax = fa if fa and x.shape[0] % dsize == 0 else None
    s_ax = "model" if msize > 1 and x.shape[1] % msize == 0 else None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(b_ax, s_ax, None)))


def shard_logits(x):
    """(B, S, V) or (B, V) logits: vocab over the model axis (matches the
    lm_head output sharding → no reshard), batch over data."""
    mesh = current_mesh()
    if mesh is None:
        return x
    fa = fsdp_axes(mesh)
    msize = _model_size(mesh)
    dsize = 1
    for a in fa:
        dsize *= mesh.shape[a]
    b_ax = fa if fa and x.shape[0] % dsize == 0 else None
    v_ax = "model" if msize > 1 and x.shape[-1] % msize == 0 else None
    spec = P(b_ax, None, v_ax) if x.ndim == 3 else P(b_ax, v_ax)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Param specs by pytree path
# ---------------------------------------------------------------------------


def _role_spec(path: str, shape: tuple, cfg, dp_shard: bool,
               model_size: int, expert_size: int = 0) -> P:
    """PartitionSpec for one param; ``path`` is '/'-joined pytree keys.
    Leading stacked-layer dims (added by the L-stacking) are detected by
    comparing ndim with the role's base rank and left unsharded."""
    fa = fsdp_axes() if dp_shard else None
    M = "model"

    def pad(spec_tail: tuple, ndim: int) -> P:
        lead = ndim - len(spec_tail)
        return P(*([None] * lead), *spec_tail)

    nd = len(shape)
    # --- scalars / norms / biases: replicated ---
    if nd <= 1 or "scale" in path or "bias" in path or path.endswith("/b") \
            or "conv_b" in path or "/D" in path or "dt_b" in path:
        return P(*([None] * nd))
    # --- embeddings / lm head ---
    if "embed" in path:
        return pad((M, fa), nd)       # (V, D)
    if "lm_head" in path:
        return pad((fa, M), nd)       # (D, V)
    # --- MoE experts (shared experts are a dense MLP, below) ---
    if ("/moe/" in path or path.startswith("moe/")) \
            and "/shared/" not in path:
        if "router" in path:
            return pad((fa, None), nd)
        # A dedicated expert axis (repro.ep all-to-all dispatch) wins:
        # E shards over "expert" and the TP axis stays free for d_ff.
        # Gated on the config opting in: a mesh may carve the axis while
        # a model keeps single-host dispatch, and expert-sharded weights
        # under the single-host gather would hand GSPMD exactly the
        # guess-a-reshard case repro.ep exists to avoid.
        if cfg.n_experts > 0 and expert_size > 0 and \
                getattr(cfg, "expert_parallel", False) and \
                cfg.n_experts % expert_size == 0:
            if "w1" in path or "w3" in path:
                return pad((EXPERT_AXIS, fa, M), nd)   # (E, d, f)
            if "w2" in path:
                return pad((EXPERT_AXIS, M, fa), nd)   # (E, f, d)
        ep = cfg.n_experts > 0 and model_size > 0 and \
            cfg.n_experts % model_size == 0
        if "w1" in path or "w3" in path:
            # (E, d, f)
            return pad((M, fa, None), nd) if ep else pad((None, fa, M), nd)
        if "w2" in path:
            # (E, f, d)
            return pad((M, None, fa), nd) if ep else pad((None, M, fa), nd)
    # --- attention ---
    if "/wq/" in path or "/wk/" in path or "/wv/" in path:
        return pad((fa, M), nd)
    if "/wo/" in path:
        return pad((M, fa), nd)
    # --- mamba ---
    if "in_proj" in path:
        return pad((fa, M), nd)       # (D, 2*Di): Di → model
    if "out_proj" in path:
        return pad((M, fa), nd)       # (Di, D)
    if "x_proj" in path:
        return pad((M, None), nd)     # (Di, dtr+2N)
    if "dt_proj" in path:
        return pad((None, M), nd)     # (dtr, Di)
    if "conv_w" in path:
        return pad((None, M), nd)     # (cw, Di)
    if "A_log" in path:
        return pad((M, None), nd)     # (Di, N)
    # --- dense mlp ---
    if "/w1/" in path or "/w3/" in path or path.endswith("/w1") \
            or path.endswith("/w3"):
        return pad((fa, M), nd)
    if "/w2/" in path or path.endswith("/w2"):
        return pad((M, fa), nd)
    if path.endswith("/w"):
        # generic dense inside attn/mlp dicts handled above via parent name
        return pad((fa, M), nd)
    return P(*([None] * nd))


def param_specs_tree(shapes_tree, cfg, *, dp_shard: bool = True):
    """Map a ShapeDtypeStruct pytree to PartitionSpecs (same structure)."""
    mesh = current_mesh()
    model_size = _model_size(mesh) if mesh is not None else 0
    expert_size = expert_axis_size(mesh)

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in node.items()}
        return _role_spec(path, node.shape, cfg, dp_shard, model_size,
                          expert_size)

    return walk(shapes_tree, "")


def named_shardings(shapes_tree, cfg, *, dp_shard: bool = True):
    mesh = current_mesh()
    assert mesh is not None, "named_shardings requires a mesh context"
    specs = param_specs_tree(shapes_tree, cfg, dp_shard=dp_shard)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
