"""Sharding rules: map every parameter / input / cache leaf to a PartitionSpec.

Mesh axes (see launch/mesh.py): ``("pod", "data", "model")`` multi-pod or
``("data", "model")`` single-pod.

- tensor parallelism on ``model``: attention heads, FFN hidden, experts, vocab
- FSDP on ``data``: the d_model-sized dim of each weight (ZeRO-3-style; XLA
  inserts per-layer all-gathers inside the scan-over-layers loop)
- pure DP on ``pod``: params replicated, gradients all-reduced across pods;
  optimizer moments are additionally sharded over ``pod`` where divisible
  (ZeRO-1 across pods)
- batch on ``("pod","data")``; for batch-1 long-context decode the cache
  sequence dim shards over ``data`` instead.

Every rule degrades to replication when a dim is not divisible by the axis
size (e.g. whisper's vocab 51865 on model=16) — correctness first, the
roofline/§Perf loop then attacks what this leaves on the table.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _fits(dim: int, mesh: Mesh, name) -> Optional[object]:
    if name is None:
        return None
    if isinstance(name, tuple):  # combined axes (fsdp_only profile)
        n = 1
        for a in name:
            if a not in mesh.axis_names:
                return None
            n *= mesh.shape[a]
        if dim % n == 0 and n > 1:
            return name
        # fall back to the first axis alone
        return _fits(dim, mesh, name[0])
    if name in mesh.axis_names and dim % mesh.shape[name] == 0 \
            and mesh.shape[name] > 1:
        return name
    return None


def dp_axes(mesh: Mesh, profile: str = "tp"):
    """Batch axes: ("pod","data") (+"model" in the fsdp_only profile)."""
    names = (("pod", "data", "model") if profile == "fsdp_only"
             else ("pod", "data"))
    return tuple(a for a in names if a in mesh.axis_names)


def batch_spec(mesh: Mesh, batch: int, extra_dims: int = 1,
               profile: str = "tp") -> P:
    """Spec for (B, ...) activations: shard B over as many dp axes as divide."""
    axes = []
    rem = batch
    for a in dp_axes(mesh, profile):
        if rem % mesh.shape[a] == 0:
            axes.append(a)
            rem //= mesh.shape[a]
    lead = tuple(axes) if axes else None
    return P(lead, *([None] * extra_dims))


# ---------------------------------------------------------------------------
# Parameter rules, keyed on tree-path names
# ---------------------------------------------------------------------------

# (matched path key) -> (dim roles), roles: "fsdp" | "tp" | None per dim,
# for the *unstacked* (per-layer) shape; a stacked leading layer dim gets None.
_RULES = {
    # embeddings / heads: vocab on tp; embed dim NOT fsdp-sharded (a gather
    # from a 2-way-sharded table forces involuntary full remat in GSPMD)
    "table": ("tp", None),
    "lm_head.w": ("fsdp", "tp"),
    # attention
    "wq.w": ("fsdp", "tp"), "wk.w": ("fsdp", "tp"), "wv.w": ("fsdp", "tp"),
    "wq.b": ("tp",), "wk.b": ("tp",), "wv.b": ("tp",),
    "wo.w": ("tp", "fsdp"), "wo.b": (None,),
    # MLA
    "wq_a.w": ("fsdp", "tp"), "wq_b.w": ("fsdp", "tp"),
    "wkv_a.w": ("fsdp", None), "wkv_b.w": ("fsdp", "tp"),
    # MLP
    "w_gate": ("fsdp", "tp"), "w_in": ("fsdp", "tp"), "w_out": ("tp", "fsdp"),
    # MoE: experts sharded on E only (pure expert parallelism). FSDP-sharding
    # the d dims too made GSPMD all-reduce the (E, C, d_ff) dispatch
    # activations (346 MB x2 per layer per microbatch measured) instead of
    # all-gathering the 65 MB of local expert weights — see §Perf hillclimb 2.
    "experts.w_gate": ("tp", None, None),
    "experts.w_in": ("tp", None, None),
    "experts.w_out": ("tp", None, None),
    "router": (None, None),
    # mamba
    "in_proj": ("fsdp", "tp"), "out_proj": ("tp", "fsdp"),
    "conv_w": ("tp", None), "conv_b": ("tp",),
    "x_proj": ("tp", None), "dt_w": (None, "tp"),
    "A_log": ("tp", None), "D": ("tp",),
    # mLSTM / sLSTM (bare (NH, DH, DH) block-diagonal projections)
    "up_proj": ("fsdp", "tp"), "down_proj": ("tp", "fsdp"),
    "wq": (None, "tp", None), "wk": (None, "tp", None),
    "wv": (None, "tp", None),
    "w_if.w": ("tp", None), "w_if.b": (None,),
    "r_z": (None, "tp", None), "r_i": (None, "tp", None),
    "r_f": (None, "tp", None), "r_o": (None, "tp", None),
    "ff_up": ("fsdp", "tp"), "ff_down": ("tp", "fsdp"),
    "w_in.w": ("fsdp", "tp"), "w_in.b": ("tp",),
}

_AXIS_FOR_ROLE = {"fsdp": "data", "tp": "model"}
_AXIS_FOR_ROLE_FSDP_ONLY = {"fsdp": ("data", "model"), "tp": None}

# per-run override: expert-dim axes for "experts.*" leaves ("model" default;
# ("data","model") for 2-D EP — set from ArchConfig.moe_expert_axes)
_EP_AXES = ("model",)


def set_moe_expert_axes(axes: str) -> None:
    global _EP_AXES
    _EP_AXES = ("data", "model") if axes == "data_model" else ("model",)


def _path_names(path) -> list:
    return [str(getattr(k, "key", getattr(k, "name", k))) for k in path]


def param_pspec(path, leaf, mesh: Mesh, *, stacked_under: str = "blocks",
                profile: str = "tp") -> P:
    """Assign a PartitionSpec to one parameter leaf by its tree path."""
    names = _path_names(path)
    # match the most specific rule (all dotted parts present in the path)
    best = None
    for key, roles in _RULES.items():
        parts = key.split(".")
        if all(p in names for p in parts):
            if best is None or len(key) > len(best[0]):
                best = (key, roles)
    shape = leaf.shape
    stacked = "blocks" in names  # decoder + encoder stacks are scan-stacked
    if best is None:
        roles = tuple([None] * (len(shape) - (1 if stacked else 0)))
    else:
        roles = best[1]
    role_map = dict(_AXIS_FOR_ROLE_FSDP_ONLY if profile == "fsdp_only"
                    else _AXIS_FOR_ROLE)
    if best is not None and best[0].startswith("experts."):
        role_map["tp"] = _EP_AXES if len(_EP_AXES) > 1 else _EP_AXES[0]
    specs = []
    offset = 0
    if stacked:
        specs.append(None)  # layer-stack dim
        offset = 1
    for i in range(offset, len(shape)):
        ridx = i - offset
        role = roles[ridx] if ridx < len(roles) else None
        ax = role_map.get(role)
        specs.append(_fits(shape[i], mesh, ax) if ax else None)
    return P(*specs)


def param_specs(abstract_params, mesh: Mesh, profile: str = "tp"):
    """Pytree of PartitionSpec matching an (abstract) param pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_pspec(path, leaf, mesh, profile=profile),
        abstract_params)


def opt_state_specs(abstract_opt_state, mesh: Mesh, pspecs):
    """Moments shard like params, then widen over axes the param leaves
    unused ('pod' always — ZeRO-1 across pods; 'data' too, which matters for
    EP-only expert weights whose d dims are unsharded)."""
    def mom(spec_tree):
        def widen(path, leaf):
            base = _lookup(pspecs, path)
            if base is None:
                return P()
            parts = list(base) + [None] * (len(leaf.shape) - len(base))
            used = set()
            for cur in parts:
                for a in (cur if isinstance(cur, tuple) else (cur,)):
                    if a:
                        used.add(a)
            for ax in ("pod", "data"):
                if ax not in mesh.axis_names or ax in used:
                    continue
                for i, (cur, dim) in enumerate(zip(parts, leaf.shape)):
                    if cur is None and dim % mesh.shape[ax] == 0 and dim > 1:
                        parts[i] = ax
                        used.add(ax)
                        break
            return P(*parts)
        return jax.tree_util.tree_map_with_path(widen, spec_tree)

    mu = mom(abstract_opt_state.mu)
    nu = mom(abstract_opt_state.nu)
    return type(abstract_opt_state)(step=P(), mu=mu, nu=nu)


def _lookup(tree, path):
    node = tree
    for k in path:
        key = getattr(k, "key", getattr(k, "name", None))
        if key is None:
            return None
        try:
            node = node[key]
        except (KeyError, TypeError, IndexError):
            return None
    return node if isinstance(node, P) else None


# ---------------------------------------------------------------------------
# Cache specs (decode)
# ---------------------------------------------------------------------------

def cache_pspec(path, leaf, mesh: Mesh, batch: int) -> P:
    """KV/state cache leaves. Batch shards over dp axes where divisible;
    KV heads over 'model' where divisible; whatever axes remain unused go to
    the sequence dim (sequence-parallel cache — a 40L MHA kv=20 cache at
    32k x 128 batch is 1.7 TB global; every idle mesh axis matters)."""
    names = _path_names(path)
    stacked = "blocks" in names
    shape = leaf.shape
    specs = [None] * len(shape)
    bdim = 1 if stacked else 0
    used = set()
    # batch across dp axes
    axes = []
    rem = shape[bdim]
    for a in dp_axes(mesh):
        if rem % mesh.shape[a] == 0 and mesh.shape[a] > 1:
            axes.append(a)
            used.add(a)
            rem //= mesh.shape[a]
    if axes:
        specs[bdim] = tuple(axes) if len(axes) > 1 else axes[0]
    # kv heads on model when divisible: (..., S, KH, hd)
    if any(k in ("k", "v", "mk", "mv") for k in names) \
            and len(shape) >= bdim + 3 and "model" not in used:
        kh = shape[bdim + 2]
        if _fits(kh, mesh, "model"):
            specs[bdim + 2] = "model"
            used.add("model")
    # remaining axes -> sequence dim (seq-parallel cache)
    is_seq_cache = any(k in ("k", "v", "ckv", "krope") for k in names)
    if is_seq_cache and len(shape) > bdim + 1:
        seq_axes = []
        rem = shape[bdim + 1]
        for a in ("data", "model"):
            if a in mesh.axis_names and a not in used \
                    and mesh.shape[a] > 1 and rem % mesh.shape[a] == 0:
                seq_axes.append(a)
                used.add(a)
                rem //= mesh.shape[a]
        if seq_axes:
            specs[bdim + 1] = (tuple(seq_axes) if len(seq_axes) > 1
                               else seq_axes[0])
    return P(*specs)


def cache_specs(abstract_cache, mesh: Mesh, batch: int):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: cache_pspec(path, leaf, mesh, batch),
        abstract_cache)


def to_shardings(spec_tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# IALS partition rules (the unified whole-horizon engine, core/engine.py)
# ---------------------------------------------------------------------------
#
# The engine's state layout is fixed by construction: every state leaf is
# (B, ...) single-agent or (B, A, ...) multi-agent (``_unflat`` guarantees
# the agent axis is dim 1 on every leaf), PPO rollout-state leaves follow
# the same convention (frames (B, [A,] k, d), t_in_ep (B,)), and streamed
# leaves prepend a horizon axis ((T, B, [A,] ...)). The rules:
#
# - env lanes (B) shard over the data-parallel axes ("pod", "data"), plus
#   "model" when the agent axis leaves it idle — rollouts are
#   embarrassingly parallel over lanes, so every divisible mesh axis is a
#   free throughput multiplier;
# - the agent axis (A) and the stacked per-agent AIP weights (leading
#   (A, ...) leaves) co-shard over "model": each device owns its agents'
#   lanes AND those agents' weights, so the per-agent weight indexing at
#   the kernel boundary stays local;
# - PPO policy/optimizer params replicate (pure DP — gradients all-reduce).
#
# Every rule degrades to replication when a dim does not divide its axis
# (A ∈ {25, 36} on a 16-wide "model" axis replicates; A=36 on 2 shards).

IALS_LANE_AXES = ("pod", "data")
IALS_AGENT_AXIS = "model"


def mesh_size(mesh) -> int:
    """Device count of a Mesh (duck-typed: only ``.shape`` consulted)."""
    n = 1
    for v in dict(mesh.shape).values():
        n *= v
    return n


def ials_lane_axes(batch: int, n_agents: int, mesh: Mesh):
    """-> (lane_axes, agent_axis | None): which mesh axes the env-lane dim
    and the agent dim take, with divisibility fallback. The two are
    decided together so lanes can absorb an idle "model" axis."""
    agent_ax = None
    if (n_agents > 1 and IALS_AGENT_AXIS in mesh.axis_names
            and mesh.shape[IALS_AGENT_AXIS] > 1
            and n_agents % mesh.shape[IALS_AGENT_AXIS] == 0):
        agent_ax = IALS_AGENT_AXIS
    lane = []
    rem = batch
    cand = IALS_LANE_AXES + (() if agent_ax else (IALS_AGENT_AXIS,))
    for a in cand:
        if a in mesh.axis_names and mesh.shape[a] > 1 \
                and rem % mesh.shape[a] == 0:
            lane.append(a)
            rem //= mesh.shape[a]
    return tuple(lane), agent_ax


def _lead(axes):
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def _pspec(specs, ndim) -> P:
    """Pad to ndim, then trim trailing Nones (a fully-replicated leaf is
    the canonical P())."""
    specs = list(specs) + [None] * (ndim - len(specs))
    while specs and specs[-1] is None:
        specs.pop()
    return P(*specs)


def ials_state_pspec(leaf, mesh: Mesh, n_agents: int) -> P:
    """One engine-state / rollout-state leaf -> PartitionSpec. Dim 0 is
    the env-lane (B) dim; dim 1 is the agent dim when the leaf carries it
    (multi-agent leaves have ``shape[1] == n_agents`` by the engine's
    ``_unflat`` layout); everything else replicates."""
    shape = getattr(leaf, "shape", ())
    if len(shape) == 0:
        return P()
    lane, agent_ax = ials_lane_axes(shape[0], n_agents, mesh)
    specs = [_lead(lane)]
    if (n_agents > 1 and len(shape) >= 2 and shape[1] == n_agents
            and agent_ax is not None):
        specs.append(agent_ax)
    return _pspec(specs, len(shape))


def ials_state_specs(state, mesh: Mesh, n_agents: int = 1):
    """PartitionSpec pytree for an engine ``IALSState`` (or a PPO
    ``RolloutState`` — any pytree following the (B, [A,] ...) layout)."""
    return jax.tree_util.tree_map(
        lambda l: ials_state_pspec(l, mesh, n_agents), state)


def ials_stream_pspec(leaf, mesh: Mesh, batch: int, n_agents: int) -> P:
    """A streamed (T, B, [A,] ...) leaf (actions, Gumbel noise, bulk env
    noise, T-stacked reset states): time replicated, then the state rule
    shifted one dim right."""
    shape = getattr(leaf, "shape", ())
    if len(shape) <= 1:
        return P()
    lane, agent_ax = ials_lane_axes(batch, n_agents, mesh)
    specs = [None, _lead(lane) if shape[1] == batch else None]
    if (n_agents > 1 and len(shape) >= 3 and shape[2] == n_agents
            and agent_ax is not None and shape[1] == batch):
        specs.append(agent_ax)
    return _pspec(specs, len(shape))


def ials_stream_specs(tree, mesh: Mesh, batch: int, n_agents: int = 1):
    return jax.tree_util.tree_map(
        lambda l: ials_stream_pspec(l, mesh, batch, n_agents), tree)


def ials_aip_param_specs(params, mesh: Mesh, n_agents: int = 1,
                         batch: int = 0):
    """Stacked per-agent AIP weights co-shard with the agent axis: each
    (A, ...) leaf puts A on the same axis the state's agent dim took
    (replicated when A does not divide). Single-agent AIPs replicate."""
    _, agent_ax = ials_lane_axes(batch or 1, n_agents, mesh)

    def spec(leaf):
        shape = getattr(leaf, "shape", ())
        if (n_agents > 1 and len(shape) >= 1 and shape[0] == n_agents
                and agent_ax is not None):
            return _pspec([agent_ax], len(shape))
        return P()

    return jax.tree_util.tree_map(spec, params)


def ials_kernel_specs(batch: int, n_agents: int, mesh: Mesh) -> Dict:
    """The PartitionSpec of each operand kind of the engine's
    lane-parallel kernel route (the ``shard_map`` in core/engine.py),
    each a prefix for a whole subtree: "state" (B, [A,] ...) leaves,
    "stream" (T, B, [A,] ...) leaves, "lanes" per-env (B,) vectors,
    "agent" stacked (A, ...) AIP weights, "rep" replicated. Same lane
    and agent axes as ``ials_state_pspec``."""
    lane, agent_ax = ials_lane_axes(batch, n_agents, mesh)
    ag = [agent_ax] if n_agents > 1 else []
    return {"state": _pspec([_lead(lane)] + ag, 1 + len(ag)),
            "stream": _pspec([None, _lead(lane)] + ag, 2 + len(ag)),
            "lanes": _pspec([_lead(lane)], 1),
            "agent": _pspec([agent_ax], 1) if n_agents > 1 else P(),
            "rep": P()}


def ials_replicated_specs(params):
    """PPO policy / optimizer params: replicated everywhere (pure DP)."""
    return jax.tree_util.tree_map(lambda _: P(), params)


def constrain_ials_state(state, mesh: Mesh, n_agents: int = 1):
    """``with_sharding_constraint`` an engine/rollout state onto the IALS
    rules — a no-op on a trivial (size-1) mesh, so the single-device
    program is bitwise-unchanged."""
    if mesh is None or mesh_size(mesh) == 1:
        return state
    return jax.tree_util.tree_map(
        lambda l: jax.lax.with_sharding_constraint(
            l, NamedSharding(mesh, ials_state_pspec(l, mesh, n_agents))),
        state)


def shard_ials_state(state, mesh: Mesh, n_agents: int = 1):
    """``device_put`` an already-materialized state across the mesh (the
    eager-side twin of ``constrain_ials_state``)."""
    if mesh is None or mesh_size(mesh) == 1:
        return state
    return jax.tree_util.tree_map(
        lambda l: jax.device_put(
            l, NamedSharding(mesh, ials_state_pspec(l, mesh, n_agents))),
        state)
