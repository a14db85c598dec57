"""Logical-axis sharding rules (MaxText-style) + the `shard` activation hint.

Model code never names mesh axes.  It tags activations with *logical* axis
names (``shard(x, ("batch", "seq", "heads", None))``) and parameters are
matched by *path pattern* (``spec_for_param``).  A context
(:func:`use_mesh_rules`) binds logical names to physical mesh axes; outside
the context every hint is a no-op, so smoke tests on 1 CPU device run the
exact same model code the 512-chip dry-run lowers.

Divisibility fallback: a logical axis is only mapped if the dimension is
divisible by the product of the mesh axis sizes it maps to — otherwise the
dimension stays replicated (recorded per-arch by the dry-run; e.g. 28 query
heads on a 16-way `model` axis fall back to replication, and the MLP `mlp`
axis carries the tensor parallelism instead).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Mapping, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisNames = Union[str, Tuple[str, ...], None]

# default logical → mesh binding (single- and multi-pod; missing mesh axes
# are dropped automatically, so "pod" is harmless on the single-pod mesh)
DEFAULT_RULES: Mapping[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": (),                 # replicated by default; prefill may use model
    "kv_seq": ("model",),      # decode KV cache sequence axis
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "experts": ("model",),
    "vocab": ("model",),
    "embed": ("data",),        # FSDP axis for parameters
    "embed_tp": ("model",),    # TP side of 2D-sharded giant params
    "state": ("model",),       # SSM / RG-LRU width
}


# Inference rules: identical to DEFAULT_RULES except parameters are NOT
# FSDP-sharded over `data` — serving has no optimizer state, so ZeRO-style
# weight sharding only adds a per-layer all-gather to every decode step.
# Weights live model-sharded (TP dims); `data` carries the batch only.
INFERENCE_RULES: Mapping[str, Tuple[str, ...]] = dict(
    DEFAULT_RULES, embed=(), embed_tp=("model",))


# Weight-replicated sequence parallelism for *serving small models*
# (prefill): activations shard their sequence over `model`, parameters are
# replicated (no optimizer states at inference), and attention's KV
# all-gather replaces the two TP all-reduces per layer — §Perf iteration 4.
PREFILL_SP_RULES: Mapping[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": ("model",),
    "kv_seq": ("model",),
    "heads": (),
    "kv_heads": (),
    "mlp": (),
    "experts": ("model",),   # MoE experts still partition over model
    "vocab": (),
    "embed": (),
    "embed_tp": (),
    "state": (),
}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Mapping[str, Tuple[str, ...]] = DEFAULT_RULES
        self.partition_disabled: bool = False


_ctx = _Ctx()


# The last mesh any trace ran under.  jax's tracing cache is keyed on the
# function and argument avals — NOT on the mesh a sharding constraint
# captured — so rebinding a different mesh (elastic restart, reshard-on-
# load) would silently reuse jaxprs pinned to the old device set.  The
# record is deliberately process-global (not per-_Ctx/thread) because the
# caches it guards are process-global; the cost is a full clear whenever
# the bound mesh changes, which only mesh-alternating workloads pay.
_last_bound_mesh = [None]


@contextlib.contextmanager
def use_mesh_rules(mesh: Optional[Mesh],
                   rules: Optional[Mapping[str, Tuple[str, ...]]] = None):
    prev = (_ctx.mesh, _ctx.rules)
    def _bind(m):
        if m is not None and _last_bound_mesh[0] is not None \
                and m != _last_bound_mesh[0]:
            jax.clear_caches()
        if m is not None:
            _last_bound_mesh[0] = m

    _bind(mesh)
    _ctx.mesh = mesh
    _ctx.rules = dict(rules) if rules is not None else DEFAULT_RULES
    try:
        yield
    finally:
        _ctx.mesh, _ctx.rules = prev
        # traces after exit run under the restored mesh; keep the record
        # honest so re-entering the inner mesh still invalidates
        _bind(prev[0])


def active_mesh() -> Optional[Mesh]:
    return _ctx.mesh


# --------------------------------------------------------------------------
# partitioned-kernel mesh (the Maple PE-array axis)
# --------------------------------------------------------------------------

# mesh axes the partitioned Maple kernels shard execution over — the
# device-level realization of the paper's §V spatial PE array.
# PARTITION_AXIS carries the block-row split (plan metadata + payload);
# COL_AXIS carries the dense operand's N-panel split (B is sharded, not
# replicated, along it — the output concatenates panels back).
PARTITION_AXIS = "shard"
COL_AXIS = "col"


def partition_mesh(n_shards: int, n_col_shards: int = 1,
                   ) -> Tuple[Optional[Mesh],
                              Optional[Union[str, Tuple[str, str]]]]:
    """Mesh for a :class:`~repro.kernels.partition.PartitionedSpmmPlan`.

    Returns ``(mesh, axes)`` where ``axes`` is the ``PARTITION_AXIS``
    name for a 1-D request (``n_col_shards == 1`` — unchanged contract)
    or the ``(PARTITION_AXIS, COL_AXIS)`` pair for a 2-D request.

    Resolution order:

    1. ``n_shards * n_col_shards <= 1`` — no mesh; the executor runs the
       stacked shard loop on one device (the planning math is identical
       either way);
    2. the **bound mesh context** (``use_mesh_rules``) carries a
       ``PARTITION_AXIS`` axis — reuse it, so partitioned kernels compose
       with a larger training/serving mesh that reserved the partition
       axes.  A bound mesh that carries the axis but at the *wrong size*
       (or lacks a ``COL_AXIS`` that a 2-D request needs) **raises** —
       never a silent fall-through to a private mesh, which would execute
       on a different device set than the one the caller reserved;
    3. otherwise build a private mesh over the first
       ``n_shards * n_col_shards`` of ``jax.local_devices()`` — 1-D over
       ``PARTITION_AXIS``, or ``(n_shards, n_col_shards)`` over
       ``(PARTITION_AXIS, COL_AXIS)`` when column panels are requested;
    4. fewer local devices than the request — ``(None, None)``: the
       executor falls back to the single-device stacked loop, which
       computes the *same* result (a plan built for 8 shards stays valid
       on a 1-device box; tests rely on this to compare both paths
       bit-for-bit).
    """
    if n_col_shards < 1:
        raise ValueError(f"n_col_shards={n_col_shards} < 1")
    total = n_shards * n_col_shards
    if total <= 1 or _ctx.partition_disabled:
        return None, None
    axes = (PARTITION_AXIS, COL_AXIS) if n_col_shards > 1 else PARTITION_AXIS
    ctx = _ctx.mesh
    if ctx is not None and PARTITION_AXIS in ctx.shape:
        if ctx.shape[PARTITION_AXIS] != n_shards:
            raise ValueError(
                f"bound mesh carries a {PARTITION_AXIS!r} axis of "
                f"{ctx.shape[PARTITION_AXIS]} devices but the plan wants "
                f"n_shards={n_shards} — rebind a matching mesh or drop "
                f"the {PARTITION_AXIS!r} axis to let partition_mesh build "
                f"a private one")
        if n_col_shards > 1:
            if COL_AXIS not in ctx.shape:
                raise ValueError(
                    f"bound mesh reserves {PARTITION_AXIS!r} but has no "
                    f"{COL_AXIS!r} axis, and the plan wants "
                    f"n_col_shards={n_col_shards} column panels — bind a "
                    f"2-D ({PARTITION_AXIS!r}, {COL_AXIS!r}) mesh")
            if ctx.shape[COL_AXIS] != n_col_shards:
                raise ValueError(
                    f"bound mesh carries a {COL_AXIS!r} axis of "
                    f"{ctx.shape[COL_AXIS]} devices but the plan wants "
                    f"n_col_shards={n_col_shards}")
        return ctx, axes
    devices = jax.local_devices()
    if len(devices) < total:
        return None, None
    if n_col_shards > 1:
        grid = np.asarray(devices[:total]).reshape(n_shards, n_col_shards)
        return Mesh(grid, (PARTITION_AXIS, COL_AXIS)), axes
    return Mesh(np.asarray(devices[:n_shards]), (PARTITION_AXIS,)), axes


@contextlib.contextmanager
def local_partition_execution():
    """Force partitioned plans onto the single-device stacked loop even
    when a mesh is available.  The loop executes the identical per-shard
    kernels and epilogue, so results are bit-identical to the
    ``shard_map`` path — which is exactly what the partition tests pin by
    running both under this switch."""
    prev = _ctx.partition_disabled
    _ctx.partition_disabled = True
    try:
        yield
    finally:
        _ctx.partition_disabled = prev


def _mesh_axes_for(logical: AxisNames, mesh: Mesh) -> Optional[Tuple[str, ...]]:
    """Resolve one logical name to the mesh axes that exist on this mesh."""
    if logical is None:
        return None
    names = (logical,) if isinstance(logical, str) else logical
    out = []
    for nm in names:
        for ax in _ctx.rules.get(nm, ()):
            if ax in mesh.shape:
                out.append(ax)
    return tuple(out) or None


def _axes_size(axes: Optional[Tuple[str, ...]], mesh: Mesh) -> int:
    if not axes:
        return 1
    size = 1
    for ax in axes:
        size *= mesh.shape[ax]
    return size


def logical_spec(dims: Sequence[AxisNames], shape: Sequence[int],
                 mesh: Mesh) -> P:
    """Build a PartitionSpec, dropping axes that don't divide the dim."""
    used = set()
    spec = []
    for logical, dim in zip(dims, shape):
        axes = _mesh_axes_for(logical, mesh)
        if axes:
            axes = tuple(a for a in axes if a not in used)
        if axes and dim % _axes_size(axes, mesh) == 0:
            spec.append(axes if len(axes) > 1 else axes[0])
            used.update(axes)
        else:
            spec.append(None)
    return P(*spec)


def shard(x: jax.Array, dims: Sequence[AxisNames]) -> jax.Array:
    """Activation sharding hint; identity when no mesh context is active."""
    mesh = _ctx.mesh
    if mesh is None:
        return x
    if len(dims) != x.ndim:
        raise ValueError(f"{len(dims)} names for rank-{x.ndim} array")
    spec = logical_spec(dims, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------------------------------
# parameter rules (path-pattern → logical dims)
# --------------------------------------------------------------------------

# ordered: first match wins.  `*` entries refer to trailing dims; stacked
# scan-group leading dims are detected by rank mismatch and get None.
_PARAM_PATTERNS = (
    ("embed_tokens", ("vocab", "embed")),
    ("lm_head", ("vocab", "embed")),
    ("wq", ("embed", "heads", None)),
    ("wk", ("embed", "kv_heads", None)),
    ("wv", ("embed", "kv_heads", None)),
    ("wo", ("heads", None, "embed")),
    ("w_gate", ("embed", "mlp")),
    ("w_up", ("embed", "mlp")),
    ("w_down", ("mlp", "embed")),
    ("w_in", ("embed", "mlp")),
    ("w_out", ("mlp", "embed")),
    ("experts_gate", ("experts", "embed", None)),
    ("experts_up", ("experts", "embed", None)),
    ("experts_down", ("experts", None, "embed")),
    ("router", ("embed", None)),
    ("in_proj", ("embed", "state")),
    ("out_proj", ("state", "embed")),
    ("conv", (None, "state")),
    ("lru_input", ("embed", "state")),
    ("lru_a_gate", ("state", "state")),
    ("lru_x_gate", ("state", "state")),
    ("vis_proj", (None, "embed")),
)


def spec_for_param(path: str, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """PartitionSpec for one parameter, matched by its pytree path string."""
    if len(shape) == 0:
        return P()
    for pat, dims in _PARAM_PATTERNS:
        if pat in path:
            if len(dims) < len(shape):
                # stacked scan-group / expert leading dims: replicate them
                dims = (None,) * (len(shape) - len(dims)) + tuple(dims)
            elif len(dims) > len(shape):
                dims = dims[-len(shape):]
            return logical_spec(dims, shape, mesh)
    return P()  # norms, biases, gates: replicated


# --------------------------------------------------------------------------
# decode-state (KV cache / recurrent state) rules
# --------------------------------------------------------------------------

_STATE_PATTERNS = (
    ("cross_k", (None, "batch", "kv_seq", None, None)),
    ("cross_v", (None, "batch", "kv_seq", None, None)),
    ("k", (None, "batch", "kv_seq", None, None)),
    ("v", (None, "batch", "kv_seq", None, None)),
    ("conv", (None, "batch", None, "state")),
    ("state", (None, "batch", "state", None, None)),
    ("h", (None, "batch", "state")),
)


def spec_for_state(path: str, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """PartitionSpec for one decode-state leaf (stacked (G, ...) caches).

    KV caches shard batch over `data` and the cache sequence over `model`
    (the flash-decode layout — softmax collectives are inserted by GSPMD);
    recurrent states shard their width over `model`.
    """
    if len(shape) == 0:
        return P()
    leaf = path.rsplit("/", 1)[-1]
    for pat, dims in _STATE_PATTERNS:
        if leaf == pat or leaf.startswith(pat):
            if len(dims) < len(shape):
                dims = (None,) * (len(shape) - len(dims)) + tuple(dims)
            elif len(dims) > len(shape):
                dims = dims[-len(shape):]
            return logical_spec(dims, shape, mesh)
    return P()


def state_shardings(state, mesh: Mesh):
    flat, treedef = jax.tree_util.tree_flatten_with_path(state)
    out = []
    for path, leaf in flat:
        path_str = "/".join(str(getattr(k, "key", k)) for k in path)
        out.append(NamedSharding(
            mesh, spec_for_state(path_str, leaf.shape, mesh)))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_shardings(batch, mesh: Mesh):
    """Input batch: leading dim is the global batch."""
    def one(leaf):
        dims = ("batch",) + (None,) * (len(leaf.shape) - 1)
        return NamedSharding(mesh, logical_spec(dims, leaf.shape, mesh))
    return jax.tree_util.tree_map(one, batch)


def param_shardings(params, mesh: Mesh):
    """NamedSharding pytree for a parameter pytree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        path_str = "/".join(str(k) for k in path)
        out.append(NamedSharding(
            mesh, spec_for_param(path_str, leaf.shape, mesh)))
    return jax.tree_util.tree_unflatten(treedef, out)


def describe_param_shardings(params, mesh: Mesh) -> str:
    """Human-readable sharding table (DESIGN/dry-run reporting)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    lines = []
    for path, leaf in flat:
        path_str = "/".join(str(getattr(k, 'key', k)) for k in path)
        spec = spec_for_param(path_str, leaf.shape, mesh)
        lines.append(f"{path_str:70s} {str(leaf.shape):24s} {spec}")
    return "\n".join(lines)
