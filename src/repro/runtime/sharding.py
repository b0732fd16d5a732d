"""Parameter / input sharding rules for the production meshes.

Strategy (baseline; the §Perf loop iterates on it):
- tensor parallelism on the ``model`` axis: FFN hidden dim, attention
  heads (falling back to head_dim, then the contraction dim when head
  counts don't divide), MoE experts (expert parallelism when E >= axis),
  vocab for embed/lm_head;
- FSDP on the ``data`` axis for any leaf whose per-model-shard footprint
  exceeds a threshold (weights are all-gathered layer-by-layer under the
  scan, so the live working set stays one layer);
- batch on (``pod``, ``data``); long-context decode (batch=1) shards the
  KV-cache *sequence* dim instead (context parallelism).

All rules respect divisibility: an axis that does not divide the dim is
dropped (replicated) rather than unevenly sharded.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

FSDP_THRESHOLD_BYTES = 32 * 1024 * 1024


def client_model_mesh(clients: int, model: int, devices=None):
    """Build the federated engine's 2-D ``("clients", "model")`` mesh.

    ``clients`` cohort shards x ``model`` tensor-parallel shards; the
    round engine runs its global block body under GSPMD on this mesh —
    the cohort axis partitions over "clients" and phi's per-leaf
    model-axis shardings (a ModelPartitioner's specs) flow through the
    block scan, so in-loop model collectives stay compiler-scheduled.
    Uses the first ``clients * model`` devices.
    """
    if clients < 1 or model < 1:
        raise ValueError(f"mesh extents must be >= 1, got "
                         f"clients={clients}, model={model}")
    devices = list(jax.devices() if devices is None else devices)
    need = clients * model
    if len(devices) < need:
        raise ValueError(
            f"client_model_mesh needs {clients}x{model}={need} devices, "
            f"have {len(devices)}; on CPU force host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    grid = np.array(devices[:need]).reshape(clients, model)
    return jax.sharding.Mesh(grid, ("clients", "model"))


@dataclasses.dataclass(frozen=True)
class ModelPartitioner:
    """Per-architecture parameter-partitioning rules for the model axis.

    ``rules(path, shape, mesh) -> PartitionSpec`` maps one param leaf to
    its spec (Levanter-style: shard attention/MLP/expert weight matrices
    on "model", replicate norms/biases). Identity (equality, hash, and
    the checkpoint fingerprint) is the ``name`` alone, so a partitioner
    can be recorded in round-state snapshots and runner-cache keys.
    """
    name: str
    # None -> the shared default rules (param_spec, defined below).
    rules: Callable[[str, Tuple[int, ...], Any], P] = dataclasses.field(
        default=None, compare=False)

    def _rules(self):
        return param_spec if self.rules is None else self.rules

    def spec(self, path, shape: Tuple[int, ...], mesh) -> P:
        """Spec for one leaf; ``path`` is a "a.b.c" string or a raw
        jax key path (as handed to tree_map_with_path callbacks)."""
        if not isinstance(path, str):
            path = _path_str(path)
        return self._rules()(path, shape, mesh)

    def shardings(self, params, mesh):
        """Pytree of NamedSharding for ``params`` under these rules."""
        rules = self._rules()
        def leaf_spec(path, leaf):
            return NamedSharding(
                mesh, rules(_path_str(path), np.shape(leaf), mesh))
        return jax.tree_util.tree_map_with_path(leaf_spec, params)


_PARTITIONERS: Dict[str, ModelPartitioner] = {}


def register_partitioner(name: str, rules=None) -> ModelPartitioner:
    """Register (or fetch, when rules is None and it exists) a
    ``ModelPartitioner``. Registering an existing name with different
    rules raises — identity is the name, so it must stay unambiguous."""
    if rules is None:
        rules = param_spec
    existing = _PARTITIONERS.get(name)
    if existing is not None:
        if existing.rules is not rules:
            raise ValueError(f"partitioner {name!r} already registered "
                             "with different rules")
        return existing
    p = ModelPartitioner(name=name, rules=rules)
    _PARTITIONERS[name] = p
    return p


def partitioner_for(arch: str) -> ModelPartitioner:
    """The registered partitioner for an architecture family name.

    transformer / mamba2 / moe all ride the shared per-leaf
    ``param_spec`` rules (leaf names are the contract, so one rule set
    covers every shipped architecture); custom architectures register
    their own via ``register_partitioner`` (docs/PLUGINS.md §8)."""
    if arch in _PARTITIONERS:
        return _PARTITIONERS[arch]
    raise KeyError(f"no ModelPartitioner registered for {arch!r}; "
                   f"known: {sorted(_PARTITIONERS)} "
                   "(register_partitioner(name, rules) adds one)")


def per_device_param_bytes(params) -> int:
    """Analytic peak parameter bytes on ONE device: the sum over leaves
    of the per-shard footprint under each leaf's committed sharding
    (replicated leaves count full size). Backend-independent — on CPU,
    where live-buffer stats read 0, this is the number the 2-D-mesh
    memory floor is judged on."""
    total = 0
    for leaf in jax.tree.leaves(params):
        shard_shape = (leaf.sharding.shard_shape(leaf.shape)
                       if hasattr(leaf, "sharding") else np.shape(leaf))
        total += int(np.prod(shard_shape, dtype=np.int64)) * leaf.dtype.itemsize
    return total


def init_distributed(coordinator: str, num_processes: int,
                     process_id: int) -> None:
    """Join (or found) a multi-process JAX runtime, cross-host-collective
    ready.

    Must run BEFORE any other JAX call: on CPU backends the default
    collective implementation cannot execute multi-process computations
    at all ("Multiprocess computations aren't implemented on the CPU
    backend"), so this selects the gloo transport FIRST — config flags
    only take effect before backend initialization — and then calls
    ``jax.distributed.initialize``. After it returns, ``jax.devices()``
    spans every process (each host contributes its local devices, in
    process order), so the engine's 1-D "clients" mesh — whose block
    runner specs have been process-count agnostic since the mesh PR —
    picks up cross-host shards with no further changes.

    coordinator:   "host:port" of process 0's coordination service.
    num_processes: total process count in the job.
    process_id:    this process's rank in [0, num_processes).
    """
    if not 0 <= process_id < num_processes:
        raise ValueError(f"process_id={process_id} out of range for "
                         f"num_processes={num_processes}")
    if num_processes > 1:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)


def _axes(mesh):
    names = mesh.axis_names
    batch = tuple(a for a in ("pod", "data") if a in names)
    return batch, ("model" if "model" in names else None)


def _size(mesh, ax):
    if ax is None:
        return 1
    if isinstance(ax, tuple):
        s = 1
        for a in ax:
            s *= mesh.shape[a]
        return s
    return mesh.shape[ax]


def _fits(dim, mesh, ax):
    return ax is not None and dim % _size(mesh, ax) == 0


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
    return "/".join(parts)


_BASE_RANK = {
    "embed": 2, "lm_head": 2, "vision_proj": 2, "final_norm": 1,
    "wq": 3, "wk": 3, "wv": 3, "wo": 3,
    "router": 2, "w_in": 2, "w_out": 2, "b_in": 1, "b_out": 1,
    "w_z": 2, "w_x": 2, "w_B": 2, "w_C": 2, "w_dt": 2,
    "dt_bias": 1, "A_log": 1, "D": 1, "conv_w": 2, "conv_b": 1,
    "gate_norm": 1, "norm1": 1, "norm2": 1, "norm_x": 1,
}


def _base_rank(path: str, leaf: str) -> int:
    if leaf in ("w_gate", "w_up"):
        return 3 if "/moe/" in "/" + path + "/" and "shared" not in path else 2
    if leaf == "w_down":
        return 3 if "/moe/" in "/" + path + "/" and "shared" not in path else 2
    if leaf == "w_out" and "mamba" in path:
        return 2
    return _BASE_RANK.get(leaf, 2)


def param_spec(path: str, shape: Tuple[int, ...], mesh) -> P:
    """Sharding rule for one parameter leaf."""
    batch_ax, model_ax = _axes(mesh)
    data_ax = "data" if "data" in mesh.axis_names else None
    leaf_name = path.rsplit("/", 1)[-1]
    base = _base_rank(path, leaf_name)
    if len(shape) < base:  # malformed/unknown leaf: replicate
        return P(*([None] * len(shape)))
    off = len(shape) - base  # scan stacks carry leading group dims
    dims = list(shape[off:])
    spec = [None] * len(shape)
    leaf = leaf_name

    def assign(rel_idx, ax):
        spec[off + rel_idx] = ax

    if len(dims) == 0 or model_ax is None:
        pass
    elif leaf == "embed":
        if _fits(dims[0], mesh, model_ax):
            assign(0, model_ax)  # vocab
    elif leaf == "lm_head":
        if _fits(dims[1], mesh, model_ax):
            assign(1, model_ax)  # vocab
    elif leaf in ("wq", "wk", "wv"):
        # (d, N, hd): heads -> head_dim -> contraction fallback
        if _fits(dims[1], mesh, model_ax):
            assign(1, model_ax)
        elif _fits(dims[2], mesh, model_ax):
            assign(2, model_ax)
        elif _fits(dims[0], mesh, model_ax):
            assign(0, model_ax)
    elif leaf == "wo":
        # (N, hd, d)
        if _fits(dims[0], mesh, model_ax):
            assign(0, model_ax)
        elif _fits(dims[1], mesh, model_ax):
            assign(1, model_ax)
        elif _fits(dims[2], mesh, model_ax):
            assign(2, model_ax)
    elif leaf in ("w_gate", "w_up"):
        if len(dims) == 3:  # MoE experts (E, d, f)
            from repro.runtime.flags import feature
            if feature("moe2d") and not _fits(dims[0], mesh, model_ax):
                # §Perf lever: stationary 2D sharding (d->data, f->model):
                # activations all-reduce instead of FSDP weight gathers.
                if _fits(dims[1], mesh, data_ax):
                    assign(1, data_ax)
                if _fits(dims[2], mesh, model_ax):
                    assign(2, model_ax)
                return P(*spec)
            if _fits(dims[0], mesh, model_ax):
                assign(0, model_ax)       # expert parallelism
            elif _fits(dims[2], mesh, model_ax):
                assign(2, model_ax)       # fall back to hidden TP
        else:               # dense (d, f)
            if _fits(dims[1], mesh, model_ax):
                assign(1, model_ax)
    elif leaf == "w_down":
        if len(dims) == 3:  # (E, f, d)
            from repro.runtime.flags import feature
            if feature("moe2d") and not _fits(dims[0], mesh, model_ax):
                if _fits(dims[1], mesh, model_ax):
                    assign(1, model_ax)
                if _fits(dims[2], mesh, data_ax):
                    assign(2, data_ax)
                return P(*spec)
            if _fits(dims[0], mesh, model_ax):
                assign(0, model_ax)
            elif _fits(dims[1], mesh, model_ax):
                assign(1, model_ax)
        else:               # (f, d)
            if _fits(dims[0], mesh, model_ax):
                assign(0, model_ax)
    elif leaf in ("w_in",):
        if _fits(dims[1], mesh, model_ax):
            assign(1, model_ax)
    elif leaf in ("w_out",):
        if _fits(dims[0], mesh, model_ax):
            assign(0, model_ax)
    elif leaf in ("w_z", "w_x"):      # (d, d_inner)
        if _fits(dims[1], mesh, model_ax):
            assign(1, model_ax)
    elif leaf in ("w_B", "w_C", "w_dt"):
        if _fits(dims[1], mesh, model_ax):
            assign(1, model_ax)
    elif leaf == "conv_w":            # (W, conv_dim)
        if _fits(dims[1], mesh, model_ax):
            assign(1, model_ax)
    elif leaf == "vision_proj":
        if _fits(dims[1], mesh, model_ax):
            assign(1, model_ax)
    # norms, biases, router, A_log, D, dt_bias, conv_b, gate_norm: replicated

    # ---- FSDP pass: shard one more (unassigned, divisible) dim on data ----
    if data_ax is not None:
        itemsize = 2  # bf16 dominant
        sharded = any(s is not None for s in spec)
        model_shards = _size(mesh, model_ax) if sharded else 1
        per_shard = int(np.prod(shape)) * itemsize // max(model_shards, 1)
        if per_shard > FSDP_THRESHOLD_BYTES:
            # biggest unassigned divisible dim (excluding stack dim)
            cands = [(dims[i], i) for i in range(len(dims))
                     if spec[off + i] is None and _fits(dims[i], mesh, data_ax)]
            if cands:
                _, best = max(cands)
                assign(best, data_ax)
    return P(*spec)


def param_shardings(params, mesh):
    """Pytree of NamedSharding matching ``params``."""
    def leaf_spec(path, leaf):
        return NamedSharding(mesh, param_spec(_path_str(path),
                                              np.shape(leaf), mesh))
    return jax.tree_util.tree_map_with_path(leaf_spec, params)


# ---------------------------------------------------------------------------
# input shardings
# ---------------------------------------------------------------------------

def batch_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def token_spec(mesh, batch_size, extra_dims=1, leading=0):
    """(batch, seq...) arrays: shard batch when divisible."""
    b_ax = batch_axes(mesh)
    ax = b_ax if b_ax and batch_size % _size(mesh, b_ax) == 0 else None
    return P(*([None] * leading + [ax] + [None] * extra_dims))


def attn_cache_spec(mesh, ndim, batch_size, seq_len) -> P:
    """(..., B, S, Kv, hd): batch on data axes when divisible, sequence on
    the remaining axes (context parallelism) — the KV cache is the decode
    memory hog, so we spread it over every available axis."""
    b_ax = batch_axes(mesh)
    model_ax = "model" if "model" in mesh.axis_names else None
    spec = [None] * ndim
    b_i, s_i = ndim - 4, ndim - 3
    seq_axes = []
    if b_ax and batch_size % _size(mesh, b_ax) == 0:
        spec[b_i] = b_ax
    else:
        seq_axes.extend(b_ax)
    if model_ax:
        seq_axes.append(model_ax)
    seq_axes = tuple(seq_axes)
    if seq_axes and seq_len % _size(mesh, seq_axes) == 0:
        spec[s_i] = seq_axes
    return P(*spec)


DEFAULT_PARTITIONER = register_partitioner("default")
# The shipped architecture families share one per-leaf rule set (leaf
# NAMES are the contract: wq/wk/wv/wo, w_in/w_out, experts, mamba
# projections), so their partitioners alias the same rules under
# distinct, fingerprint-stable names.
for _arch in ("transformer", "mamba2", "moe"):
    register_partitioner(_arch)
del _arch


def mamba_cache_spec(mesh, leaf_name, ndim, batch_size, head_count) -> P:
    """ssm state (..., B, H, P, N) or conv state (..., B, W, C)."""
    b_ax = batch_axes(mesh)
    model_ax = "model" if "model" in mesh.axis_names else None
    base = 4 if leaf_name == "ssm" else 3
    off = ndim - base
    spec = [None] * ndim
    if b_ax and batch_size % _size(mesh, b_ax) == 0:
        spec[off] = b_ax
    if (model_ax and leaf_name == "ssm"
            and head_count % _size(mesh, model_ax) == 0):
        spec[off + 1] = model_ax
    return P(*spec)
