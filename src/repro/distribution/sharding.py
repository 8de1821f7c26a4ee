"""Parameter/activation sharding rules (logical-axis style), plus the
experiment-axis data parallelism of the vectorized sweep engine.

Rules are keyed on parameter leaf names (the model zoo uses stable names) and
produce ``PartitionSpec``s.  ``model_axis`` carries tensor parallelism
(attention heads / FFN hidden / experts / vocab); ``fsdp_axis`` optionally
shards the other large dim (required for llama3-405b).  Leaves with a leading
superblock-stack axis get a ``None`` prepended automatically.

``experiment_mesh`` / ``shard_experiment_axis`` serve
``repro.fed.runtime.run_batched``: a batched grid of experiments is embar-
rassingly parallel over its leading E axis, so when several local devices
are available the stacked per-experiment state is placed with E sharded over
a 1-D mesh and the jitted vmapped program runs SPMD — each device carries
E / n_devices whole experiments, no cross-device collectives.

``device_mesh`` / ``shard_device_axis`` serve the OTHER mesh of the repo —
the FL-device axis of the sharded streaming engine
(``FLConfig.device_mesh``): the K-blocked round partitions its blocks over
``device_mesh`` shards, each mesh device left-folds its own blocks, and one
deterministic cross-shard combine closes eq. (10).  The two meshes are
orthogonal by construction (a batched sweep owns the experiment axis, a
streaming round owns the FL-device axis) and are never active in the same
program — ``run_batched`` rejects ``device_mesh`` configs.
"""
from __future__ import annotations

import os
import warnings
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

EXPERIMENT_AXIS = "exp"
FL_DEVICE_AXIS = "fldev"

# set REPRO_FL_MESH=emulate to force the sharded streaming engine onto its
# emulated (no-collective) execution path even when enough local devices
# exist — the parity tests' lever.  Read at TRACE time: flip it only before
# the first run of a config, or call runtime.clear_compile_caches() after.
_EMULATE_ENV = "REPRO_FL_MESH"

# Both 1-D meshes are Auto-typed: the engine places its inputs itself
# (device_put / shard_map specs / sharding constraints).  jax.make_mesh
# defaults to Explicit axes, which put the mesh into the array types: the
# shard_map bodies then fail on closed-over params, and the batched sweep's
# scan cannot find the experiment axis.
_AUTO = (jax.sharding.AxisType.Auto,)


def experiment_mesh(num_experiments: int, *, axis_name: str = EXPERIMENT_AXIS,
                    devices=None, require: bool = False):
    """A 1-D mesh over the local devices for sharding a batched run's
    experiment axis, or ``None`` when sharding would not help: a single
    device, or a grid the device count does not divide (uneven shards would
    force padding).  ``None`` means the caller falls back to running the
    whole batch replicated on one device — the run is still correct, just
    not device-parallel.

    ``require=True`` turns the silent fallback into an actionable
    ``ValueError`` for callers that *expect* sharding to engage (tests, the
    benchmark harness): the message says which precondition failed and how
    to fix it."""
    if num_experiments < 1:
        raise ValueError(
            f"num_experiments must be >= 1, got {num_experiments}")
    devices = list(jax.local_devices() if devices is None else devices)
    if len(devices) <= 1:
        if require:
            raise ValueError(
                f"experiment-axis sharding needs > 1 local device, have "
                f"{len(devices)} — force host devices with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N or drop "
                "require=True to run replicated on one device")
        return None
    if num_experiments % len(devices) != 0:
        if require:
            raise ValueError(
                f"experiment count {num_experiments} does not divide over "
                f"{len(devices)} local devices (uneven shards would force "
                "padding) — pad the grid to a multiple of the device count, "
                "restrict jax to a dividing subset, or drop require=True to "
                "run replicated on one device")
        return None
    return jax.make_mesh((len(devices),), (axis_name,), axis_types=_AUTO,
                         devices=devices)


def device_mesh(num_shards: int, *, axis_name: str = FL_DEVICE_AXIS,
                devices=None):
    """A 1-D mesh of exactly ``num_shards`` local devices for the sharded
    streaming engine's FL-device axis, or ``None`` when the host cannot
    provide them (or ``REPRO_FL_MESH=emulate`` forces the emulated path) —
    the caller then runs the SAME shard blocking as an outer ``lax.scan``,
    bitwise-identical by the deterministic-combine contract
    (``fold_shards``)."""
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if os.environ.get(_EMULATE_ENV, "") == "emulate":
        return None
    devices = list(jax.local_devices() if devices is None else devices)
    if num_shards == 1 or len(devices) < num_shards:
        return None
    return jax.make_mesh((num_shards,), (axis_name,), axis_types=_AUTO,
                         devices=devices[:num_shards])


def shard_device_axis(tree: Any, mesh, *,
                      axis_name: str = FL_DEVICE_AXIS) -> Any:
    """``device_put`` every array leaf of ``tree`` with its leading (shard)
    axis split over ``mesh``; rank-0 leaves replicate.  The leaves must all
    carry the shard count as their leading axis — the [D, nb/D, k_block,
    ...] blocked inputs of the sharded streaming round."""
    def one(leaf):
        nd = jnp.ndim(leaf)
        spec = P() if nd == 0 else P(axis_name, *([None] * (nd - 1)))
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(one, tree)


def shard_experiment_axis(tree: Any, mesh, *,
                          axis_name: str = EXPERIMENT_AXIS) -> Any:
    """``device_put`` every array leaf of ``tree`` with its leading
    (experiment) axis sharded over ``mesh``; rank-0 leaves replicate.  The
    leaves must all carry E as their leading axis (the stacked state of
    ``run_batched``)."""
    def one(leaf):
        nd = jnp.ndim(leaf)
        spec = P() if nd == 0 else P(axis_name, *([None] * (nd - 1)))
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(one, tree)

# name -> spec WITHOUT the stack axis; 'M' = model axis, 'F' = fsdp axis
_RULES = {
    # embeddings.  NOTE: the token table is deliberately NOT fsdp-sharded:
    # a gather from a table sharded over BOTH mesh axes inside a partial-manual
    # shard_map trips an XLA SPMD-partitioner check failure (b/433785288-like;
    # minimal repro in tests/test_distribution.py) — and at TP-only sharding
    # the table is small per chip anyway (llama3-405b: 263 MB).
    "tok": ("M", None),           # vocab-sharded embedding table
    "unemb": ("F", "M"),
    "modal_proj": (None, "M"),
    # attention
    "wq": ("F", "M"), "wk": ("F", "M"), "wv": ("F", "M"),
    "bq": ("M",), "bk": ("M",), "bv": ("M",),
    "wo": ("M", "F"),
    # dense mlp (rank-2) / moe experts (rank-3) share names; see _spec_for
    "w_gate": ("F", "M"), "w_up": ("F", "M"), "w_down": ("M", "F"),
    "router": (None, None),
    # mamba
    "in_proj": ("F", "M"), "out_proj": ("M", "F"),
    "conv_w": (None, "M"), "conv_b": ("M",),
    "x_proj": ("M", None), "dt_proj_w": (None, "M"), "dt_proj_b": ("M",),
    "A_log": ("M", None), "D": ("M",),
    # xlstm
    "up": ("F", "M"), "down": ("M", "F"),
    "ogate": (None, "M"),
    "wi": ("M", None), "wf": ("M", None), "bi": (None,), "bf": (None,),
    "w_gates": ("M", None),
    "r_gates": (None, None, None, None),
    "b_gates": (None,),
    # norms
    "scale": (None,),
}

_MOE_RULES = {  # rank-3 expert-stacked weights: experts over the model axis
    "w_gate": ("M", "F", None), "w_up": ("M", "F", None), "w_down": ("M", None, "F"),
}


def _resolve(symbolic, model_axis, fsdp_axis):
    out = []
    for s in symbolic:
        if s == "M":
            out.append(model_axis)
        elif s == "F":
            out.append(fsdp_axis)
        else:
            out.append(None)
    return tuple(out)


def _spec_for(name: str, parents: tuple, ndim: int, model_axis: str,
              fsdp_axis: Optional[str]) -> P:
    rule = _RULES.get(name)
    if name in _MOE_RULES and "moe" in parents:   # expert-stacked weights
        rule = _MOE_RULES[name]
    if rule is None:
        return P()
    spec = _resolve(rule, model_axis, fsdp_axis)
    if ndim == len(spec) + 1:      # leading superblock-stack axis
        spec = (None,) + spec
    elif ndim != len(spec):
        return P()                 # unknown layout: replicate (safe default)
    return P(*spec)


def param_specs(params, *, model_axis: str = "model",
                fsdp_axis: Optional[str] = None):
    """PartitionSpec pytree for a model parameter pytree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        names = [str(e.key) for e in path if isinstance(e, jax.tree_util.DictKey)]
        name = names[-1] if names else ""
        specs.append(_spec_for(name, tuple(names[:-1]), jnp.ndim(leaf),
                               model_axis, fsdp_axis))
    return jax.tree_util.tree_unflatten(treedef, specs)


# (axes, dim size) pairs sanitize_spec has already warned about: each
# distinct drop is reported ONCE per process, not once per leaf per call —
# a sharded sweep calls sanitize_spec thousands of times on the same rules
_SANITIZE_WARNED: set = set()


def sanitize_spec(mesh, spec: P, shape) -> P:
    """Drop mesh axes from dims they don't divide (e.g. vocab 256206 on a
    16-way model axis) — replicating such a dim is always legal.  Each
    distinct drop warns once per process (``UserWarning``): a silently
    replicated dim that was *meant* to shard is a memory/perf bug the user
    should see, while the known-benign cases (that vocab) stay readable."""
    out = []
    for d, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if d < len(shape) and shape[d] % size == 0:
            out.append(entry)
        else:
            dim = shape[d] if d < len(shape) else None
            sig = (axes, size, dim)
            if sig not in _SANITIZE_WARNED:
                _SANITIZE_WARNED.add(sig)
                what = (f"dim {d} of size {dim}" if dim is not None
                        else f"dim {d} (beyond the leaf's rank {len(shape)})")
                warnings.warn(
                    f"sanitize_spec: mesh axes {axes} (size {size}) do not "
                    f"divide {what}; replicating that dim instead. "
                    "Expected for known-ragged dims (e.g. an odd vocab); if "
                    "this dim was meant to shard, fix the rule or pad the "
                    "dim. (warned once per distinct drop)",
                    UserWarning, stacklevel=2)
            out.append(None)
    return P(*out)


def named_shardings(mesh, spec_tree, like_tree=None):
    """NamedShardings for a spec pytree; with ``like_tree`` given, specs are
    sanitized against the actual leaf shapes first."""
    if like_tree is None:
        return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), spec_tree,
                                      is_leaf=lambda x: isinstance(x, P))
    return jax.tree_util.tree_map(
        lambda s, l: NamedSharding(mesh, sanitize_spec(mesh, s, l.shape)),
        spec_tree, like_tree, is_leaf=lambda x: isinstance(x, P))


def batch_specs(batch_like, axes) -> object:
    """Shard the leading (batch) dim of every batch leaf over ``axes``;
    scalars (e.g. ``pos``) replicate."""
    def one(leaf):
        if jnp.ndim(leaf) == 0:
            return P()
        return P(tuple(axes))
    return jax.tree_util.tree_map(one, batch_like)


def cache_specs(cache_like, *, batch_axes, model_axis: str, num_kv_heads: int,
                model_size: int, seq_axis: Optional[str] = None):
    """Decode-cache shardings, name-keyed like ``param_specs``.

    KV caches [n_sb,B,S,Hkv,dh] shard batch over ``batch_axes``, kv-heads over
    ``model_axis`` when divisible (else the seq axis over ``seq_axis`` for the
    context-parallel long-decode path); recurrent states shard batch + their
    big feature dim over the model axis.
    """
    kv_ok = num_kv_heads % model_size == 0
    b_ax = tuple(batch_axes) if batch_axes else None

    def spec_for(name: str, nd: int) -> P:
        if name in ("k", "v") and nd == 5:
            return P(None, b_ax, seq_axis, model_axis if kv_ok else None, None)
        if name == "ssm" and nd == 4:       # [n_sb, B, di, N]
            return P(None, b_ax, model_axis, None)
        if name == "conv" and nd == 4:      # [n_sb, B, K-1, di]
            return P(None, b_ax, None, model_axis)
        if name == "C" and nd == 5:         # [n_sb, B, H, dh, dh] mlstm memory
            return P(None, b_ax, None, model_axis, None)
        if name == "n" and nd == 4:         # [n_sb, B, H, dh] mlstm normalizer
            return P(None, b_ax, None, model_axis)
        if nd == 3:                         # [n_sb, B, di] slstm states
            return P(None, b_ax, model_axis)
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_like)
    specs = []
    for path, leaf in flat:
        name = ""
        for entry in reversed(path):
            if isinstance(entry, jax.tree_util.DictKey):
                name = str(entry.key)
                break
        specs.append(spec_for(name, jnp.ndim(leaf)))
    return jax.tree_util.tree_unflatten(treedef, specs)
