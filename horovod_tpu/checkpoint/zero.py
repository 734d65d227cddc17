"""ZeRO-1 optimizer state <-> sharded checkpoint engine bridge.

``ZeroShardedOptimizer`` state is rank-DISTINCT: each data-parallel rank
owns one flat 1/N shard of every moment.  ``broadcast_optimizer_state``
rightly refuses it; this module gives that state a durable lifecycle
instead:

* :func:`zero_init` / :func:`zero_state_specs` — build and thread the
  state through ``shard_map`` *globally* (vector leaves are the full
  padded flat buffers, partitioned over the axis), so host code can see
  every rank's shard;
* :func:`save_zero_state` — each rank writes its shard, rank 0 commits
  the manifest last (engine protocol: a partial write is never
  restorable);
* :func:`restore_zero_state` — loads a checkpoint written at world size
  N into a job running at world size M, reassembling the flat moment
  buffers from N shards and re-slicing into M — the elastic-resize path.

The mapping from inner-optimizer state leaves to parameter leaves uses
the optax convention that per-parameter trees (``mu``, ``nu``, ``trace``
...) carry the params treedef: vector leaves flatten in runs of
``len(params_leaves)``, in params-flatten order.  Every leaf is shape-
validated against the recorded true sizes, so a transform that breaks
the convention fails loudly at save time rather than corrupting state.
"""

from __future__ import annotations

import math
from typing import Any, List, NamedTuple, Optional

import numpy as np

from ..debug import flight as _flight
from . import engine as E
from . import manifest as M
from . import reshard as R


def _zero_state_type():
    from ..optimizers import _ZeroState
    return _ZeroState


def _is_zero(x) -> bool:
    return isinstance(x, _zero_state_type())


def is_zero_state(x) -> bool:
    """True iff ``x`` is a ``ZeroShardedOptimizer`` state (rank-distinct
    shards that must round-trip through this engine, never a broadcast
    or rank-0-writes path)."""
    return _is_zero(x)


def has_zero_leaves(tree) -> bool:
    """True iff any leaf of ``tree`` is ZeRO-sharded state — the single
    routing predicate shared by utils/checkpoint.py and elastic/state.py."""
    import jax
    return any(_is_zero(l) for l in
               jax.tree_util.tree_leaves(tree, is_leaf=_is_zero))


def _default_axis(axis_name):
    from ..ops import collective as C
    return C._default_axis(axis_name)


def _keystr(path) -> str:
    import jax
    return jax.tree_util.keystr(path)


def _axis_tuple(axis_name):
    """Normalize an axis argument to a tuple of axis names: ZeRO state
    may shard over ONE mesh axis (the classic dp layout) or over the
    PRODUCT of arbitrarily many (``("data", "model")`` for a 2-D mesh,
    ``("data", "model", "expert")`` / ``("data", "model", "pipe")`` for
    a third axis — every chip holds 1/world of the flat layout, so a
    mesh change across ANY axis combination, (2,2,2) → (2,2,1)
    included, is just an N→M reshard of the same flat layout; the
    peer/disk-free recovery path inherits this by construction)."""
    return axis_name if isinstance(axis_name, (tuple, list)) \
        else (axis_name,)


def _axis_world(mesh, axis_name) -> int:
    return int(np.prod([int(mesh.shape[a])
                        for a in _axis_tuple(axis_name)]))


def _rank_of_device(mesh, axis_name):
    """{device: rank along ``axis_name``} for one replica slice of the
    mesh (all other axes at position 0).  For a tuple of axes the rank
    is the row-major flattened index over them, matching
    ``lax.axis_index(tuple)`` inside ``shard_map``."""
    axes = list(mesh.axis_names)
    ais = [axes.index(a) for a in _axis_tuple(axis_name)]
    sizes = [int(mesh.shape[axes[i]]) for i in ais]
    out = {}
    dev = np.asarray(mesh.devices)
    for idx in np.ndindex(dev.shape):
        if all(idx[j] == 0 for j in range(len(idx)) if j not in ais):
            rank = 0
            for i, n in zip(ais, sizes):
                rank = rank * n + idx[i]
            out[dev[idx]] = rank
    return out


def _owned_ranks(mesh, axis_name):
    """Ranks whose shard file THIS process writes: those whose device in
    the replica slice is local.  Replicated leaves are duplicated into
    every rank's file, so ownership must come from the mesh — 'any value
    present' would make every process write (a replicated-only copy of)
    every rank's shard, racing the true owner's complete file."""
    import jax
    pidx = jax.process_index()
    return {r for d, r in _rank_of_device(mesh, axis_name).items()
            if d.process_index == pidx}


# ---------------------------------------------------------------------------
# Leaf plan: walk a pytree, classify every leaf, record true sizes
# ---------------------------------------------------------------------------

class _LeafPlan:
    """One engine leaf: its spec plus how to pull per-rank host values
    out of the live pytree leaf."""

    def __init__(self, spec: M.LeafSpec, threaded: str):
        self.spec = spec
        self.threaded = threaded  # "global" | "per-rank" | "replicated"


def _leaf_dtype(leaf) -> str:
    return str(leaf.dtype) if hasattr(leaf, "dtype") \
        else str(np.asarray(leaf).dtype)


def _plan_zero_state(z, path_prefix: str, world: int,
                     validate: bool = True) -> List[_LeafPlan]:
    import jax
    sizes_paths, _ = jax.tree_util.tree_flatten_with_path(z.sizes)
    true_sizes = [int(v) for _, v in sizes_paths]
    n_params = len(true_sizes)
    if n_params == 0:
        raise ValueError("ZeRO state carries no recorded parameter sizes; "
                         "was it produced by this version's init?")
    plans: List[_LeafPlan] = []
    for (path, leaf) in sizes_paths:
        spec = M.LeafSpec(path=path_prefix + ".sizes" + _keystr(path),
                          kind=M.REPLICATED, shape=[],
                          dtype=_leaf_dtype(leaf), true_size=1)
        plans.append(_LeafPlan(spec, "replicated"))
    inner_paths, _ = jax.tree_util.tree_flatten_with_path(z.inner)
    vec_count = 0
    for (path, leaf) in inner_paths:
        pstr = path_prefix + ".inner" + _keystr(path)
        ndim = getattr(leaf, "ndim", 0)
        if ndim == 0:
            spec = M.LeafSpec(path=pstr, kind=M.REPLICATED, shape=[],
                              dtype=_leaf_dtype(leaf), true_size=1)
            plans.append(_LeafPlan(spec, "replicated"))
            continue
        true = true_sizes[vec_count % n_params]
        vec_count += 1
        padded = true + ((-true) % world)
        size = int(np.prod(leaf.shape))
        if ndim >= 2 or (size == true and size != padded
                         and size != padded // world):
            # GSPMD-plane state (ops/gspmd.py compressed steps): the
            # moment leaves are PARAM-shaped global arrays — the XLA
            # partitioner owns their sharding, so they commit as full
            # dense values (world-invariant; single-controller commit,
            # like every replicated leaf).  A 1-D param whose size
            # happens to equal the padded flat buffer lands in the
            # "global" branch instead — identical bytes and shape
            # either way.
            spec = M.LeafSpec(path=pstr, kind=M.REPLICATED,
                              shape=list(leaf.shape),
                              dtype=_leaf_dtype(leaf), true_size=size)
            plans.append(_LeafPlan(spec, "replicated"))
            continue
        if size == padded:
            threaded = "global"
        elif size == padded // world:
            threaded = "per-rank"
        elif not validate:
            threaded = "global"  # structure-only plan (restore target)
        else:
            raise ValueError(
                f"ZeRO state leaf {pstr} has {size} elements; expected "
                f"the full padded buffer ({padded}) or one rank's shard "
                f"({padded // world}) for true size {true} at world "
                f"{world}.  Elementwise inner transforms only — see "
                "docs/checkpointing.md.")
        spec = M.LeafSpec(path=pstr, kind=M.SHARDED, shape=[true],
                          dtype=_leaf_dtype(leaf), true_size=true)
        plans.append(_LeafPlan(spec, threaded))
    if vec_count % n_params != 0:
        raise ValueError(
            f"ZeRO state under {path_prefix} has {vec_count} vector "
            f"leaves, not a multiple of the {n_params} parameter leaves; "
            "the inner transform does not follow the optax per-parameter "
            "tree convention")
    if getattr(z, "residual", None) is not None:
        # Error-feedback residuals (quantized wires): one flat fp32 run
        # per parameter leaf, rank-DISTINCT like the moments but sized
        # in TRUE elements per rank — globally (world * true,), no
        # padding (world divides the global size by construction).
        # true_size records the global size, which pins the checkpoint
        # to the writing world: an elastic N->M restore of EF residuals
        # has no meaningful reshard (each rank's error belongs to the
        # gradients IT quantized), so the fingerprint refusing the
        # cross-world restore is the correct behavior — reset the
        # residual to zeros for a world change (docs/zero.md).
        res_paths, _ = jax.tree_util.tree_flatten_with_path(z.residual)
        res_count = 0
        for (path, leaf) in res_paths:
            pstr = path_prefix + ".residual" + _keystr(path)
            true = true_sizes[res_count % n_params]
            res_count += 1
            rt = true * world
            size = int(np.prod(getattr(leaf, "shape", ()))) \
                if getattr(leaf, "shape", ()) else 1
            if size == rt:
                threaded = "global"
            elif size == true:
                threaded = "per-rank"
            elif not validate:
                threaded = "global"
            else:
                raise ValueError(
                    f"ZeRO residual leaf {pstr} has {size} elements; "
                    f"expected the global buffer ({rt}) or one rank's "
                    f"error view ({true}) for true size {true} at world "
                    f"{world}")
            spec = M.LeafSpec(path=pstr, kind=M.SHARDED, shape=[rt],
                              dtype=_leaf_dtype(leaf), true_size=rt)
            plans.append(_LeafPlan(spec, threaded))
        if res_count % n_params != 0:
            raise ValueError(
                f"ZeRO state under {path_prefix} has {res_count} "
                f"residual leaves, not a multiple of the {n_params} "
                "parameter leaves")
    return plans


def _plan_tree(tree, world: int, validate: bool = True):
    """Flatten ``tree`` (descending into ``_ZeroState`` specially) into
    ordered leaf plans + the outer flatten context for rebuilds."""
    import jax
    outer, outer_def = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=_is_zero)
    plans: List[_LeafPlan] = []
    groups = []  # per outer leaf: ("zero", n_plans, z) | ("plain", 1, leaf)
    for path, leaf in outer:
        pstr = _keystr(path)
        if _is_zero(leaf):
            zplans = _plan_zero_state(leaf, pstr, world, validate=validate)
            groups.append(("zero", len(zplans), leaf))
            plans.extend(zplans)
        else:
            shape = list(getattr(leaf, "shape", ()))
            spec = M.LeafSpec(path=pstr, kind=M.REPLICATED, shape=shape,
                              dtype=_leaf_dtype(leaf),
                              true_size=int(np.prod(shape)) if shape else 1)
            plans.append(_LeafPlan(spec, "replicated"))
            groups.append(("plain", 1, leaf))
    return plans, groups, outer_def


# ---------------------------------------------------------------------------
# Host extraction of per-rank values from live (possibly device) leaves
# ---------------------------------------------------------------------------

def _leaf_rank_values(leaf, plan: _LeafPlan, world: int, mesh, axis_name):
    """{rank: host array} for one leaf — only ranks whose data is
    addressable from this process (all of them in single-controller)."""
    import jax
    spec = plan.spec
    if plan.threaded == "replicated":
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            leaf = list(leaf.addressable_shards)[0].data
        val = np.asarray(leaf)
        return {r: val for r in range(world)}, True
    k = spec.padded_size(world) // world
    if plan.threaded == "per-rank":
        # shard_map out_specs P() threading: each device's buffer is its
        # rank's shard; np.asarray would silently read just one of them.
        if not isinstance(leaf, jax.Array):
            raise ValueError(
                f"per-rank threaded leaf {spec.path} is not a jax.Array; "
                "cannot recover the other ranks' shards")
        rank_of = _rank_of_device(mesh, axis_name)
        out = {}
        for shard in leaf.addressable_shards:
            rank = rank_of.get(shard.device)
            if rank is not None:
                out[rank] = np.asarray(shard.data).reshape(-1)
        return out, len(out) == world
    # "global" threading: the leaf IS the padded flat buffer.
    if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
        out = {}
        for shard in leaf.addressable_shards:
            data = np.asarray(shard.data).reshape(-1)
            start = shard.index[0].start or 0
            if data.size % k:
                raise ValueError(
                    f"leaf {spec.path}: addressable shard of {data.size} "
                    f"elements does not cover whole rank shards of {k}")
            for i in range(data.size // k):
                out[start // k + i] = data[i * k:(i + 1) * k]
        return out, len(out) == world
    buf = np.asarray(leaf).reshape(-1)
    return {r: buf[r * k:(r + 1) * k] for r in range(world)}, True


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def zero_state_specs(state, axis_name: Optional[str] = None):
    """``PartitionSpec`` pytree for threading a ZeRO state through
    ``shard_map``: vector moment leaves partition over the data axis
    (global flat buffers outside, per-rank shards inside), everything
    else replicated."""
    import jax
    from jax.sharding import PartitionSpec as P
    ax = _default_axis(axis_name)

    def _zero_specs(z):
        inner = jax.tree_util.tree_map(
            lambda l: P(ax) if getattr(l, "ndim", 0) >= 1 else P(),
            z.inner)
        sizes = jax.tree_util.tree_map(lambda l: P(), z.sizes)
        kw = {}
        if getattr(z, "residual", None) is not None:
            kw["residual"] = jax.tree_util.tree_map(
                lambda l: P(ax) if getattr(l, "ndim", 0) >= 1 else P(),
                z.residual)
        return type(z)(inner=inner, sizes=sizes, **kw)

    return jax.tree_util.tree_map(
        lambda l: _zero_specs(l) if _is_zero(l) else P(),
        state, is_leaf=_is_zero)


def zero_init(tx, params, mesh=None, axis_name: Optional[str] = None):
    """Initialize ZeRO state *globally threaded*: runs ``tx.init`` inside
    ``shard_map`` and returns vector leaves as full padded flat buffers
    partitioned over the axis — the layout ``save_zero_state`` and
    ``restore_zero_state`` exchange.

    ``params`` may be full (replicated) parameters — the stage-1/2
    layout — or a stage-3 sharded param state (``shard_params`` /
    :func:`zero_shard_params` output, itself ZeRO state): sharded
    inputs are threaded with their own partition specs so ``tx.init``
    sees exactly this rank's shards."""
    import jax
    from jax.sharding import PartitionSpec as P
    from ..compat import shard_map
    if mesh is None:
        from ..core import basics
        mesh = basics.mesh()
    ax = _default_axis(axis_name)
    in_specs = (zero_state_specs(params, axis_name=ax)
                if has_zero_leaves(params) else P())
    shape_probe = jax.eval_shape(
        shard_map(tx.init, mesh=mesh, in_specs=(in_specs,), out_specs=P(),
                  check_vma=False), params)
    out_specs = zero_state_specs(shape_probe, axis_name=ax)
    return jax.jit(shard_map(tx.init, mesh=mesh, in_specs=(in_specs,),
                             out_specs=out_specs, check_vma=False))(params)


def zero_shard_params(tx, params, mesh=None,
                      axis_name: Optional[str] = None):
    """Full parameters → a *globally threaded* stage-3 sharded param
    state: runs ``tx.shard_params`` inside ``shard_map`` and returns the
    params-structured flat shards as full padded buffers partitioned
    over the axis — the exact layout the checkpoint engine commits and
    the peer-recovery tier replicates (sharded params ARE ZeRO state,
    see docs/zero.md)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from ..compat import shard_map
    if getattr(tx, "shard_params", None) is None:
        raise ValueError(
            "zero_shard_params needs a ZeroShardedOptimizer "
            "transformation (stage 3) exposing shard_params")
    if mesh is None:
        from ..core import basics
        mesh = basics.mesh()
    ax = _default_axis(axis_name)
    shape_probe = jax.eval_shape(
        shard_map(tx.shard_params, mesh=mesh, in_specs=(P(),),
                  out_specs=P(), check_vma=False), params)
    out_specs = zero_state_specs(shape_probe, axis_name=ax)
    return jax.jit(shard_map(tx.shard_params, mesh=mesh, in_specs=(P(),),
                             out_specs=out_specs, check_vma=False))(params)


def _foreign_allowed() -> bool:
    import os
    return os.environ.get("HVD_TPU_CKPT_ALLOW_FOREIGN", "") == "1"


def _recorded_fingerprint(manifest: M.Manifest) -> str:
    """The manifest's stamped fingerprint; derived from its leaf specs
    for checkpoints written before the stamp existed (same hash)."""
    rec = (manifest.extra or {}).get(M.RUN_FINGERPRINT_KEY) or {}
    return rec.get("leaf_spec_sha256") or M.spec_fingerprint(
        manifest.leaves)


def _check_run_fingerprint(root: str, fp: str, direction: str) -> None:
    """Refuse to mix runs in one checkpoint directory: the engine
    validates pytree structure but cannot tell one run's moments from
    another's (docs/checkpointing.md) — the fingerprint can.  Escape
    hatch: HVD_TPU_CKPT_ALLOW_FOREIGN=1."""
    latest = E.latest_step(root)
    if latest is None:
        return
    try:
        manifest = E.read_manifest(root, latest)
    except (OSError, ValueError, KeyError):
        return
    recorded = _recorded_fingerprint(manifest)
    if recorded == fp:
        return
    if _foreign_allowed():
        from ..utils import logging as log
        log.warning(
            "checkpoint %s: run fingerprint mismatch (%s... vs this "
            "run's %s...) overridden by HVD_TPU_CKPT_ALLOW_FOREIGN=1",
            direction, recorded[:12], fp[:12])
        return
    raise ValueError(
        f"checkpoint directory {root} belongs to a different run: its "
        f"newest committed step has leaf-spec fingerprint "
        f"{recorded[:12]}..., this state fingerprints {fp[:12]}... "
        f"(different model/optimizer structure, dtypes or sizes).  "
        f"Refusing the cross-run {direction}: use a fresh "
        f"checkpoint_dir per training run, or set "
        f"HVD_TPU_CKPT_ALLOW_FOREIGN=1 to override.")


class ExtractedState(NamedTuple):
    """One commit's host-side payload: the leaf specs plus every locally
    addressable rank's per-leaf arrays — the bytes the disk shards AND
    the peer-replica tier both encode, extracted exactly once."""

    specs: List[M.LeafSpec]
    rank_values: dict             # {rank: [per-leaf host arrays]}
    world: int
    fingerprint: str              # world-size-invariant leaf-spec sha256
    mesh_shape: dict              # {axis: size} of the extracting mesh


def extract_zero_state(state, mesh=None,
                       axis_name: Optional[str] = None) -> ExtractedState:
    """Pull the per-rank host values out of a live pytree containing
    ZeRO state — the extraction half of :func:`save_zero_state`, shared
    with ``horovod_tpu.recovery``'s commit-time replication so disk
    shards and peer replicas are the same bytes by construction."""
    if mesh is None:
        from ..core import basics
        mesh = basics.mesh()
    ax = _default_axis(axis_name)
    world = _axis_world(mesh, ax)
    plans, groups, _ = _plan_tree(state, world)

    # Flight bracket: the device→host reads below block when a device
    # computation is wedged — a rank hanging HERE must attribute as
    # checkpoint-bound in a hang report, exactly like one stuck inside
    # the shard writes (debug/hang.attribute pairs checkpoint.*.begin
    # with any later checkpoint.* completion).
    _flight.record("checkpoint.extract.begin", None, world=world)
    try:
        leaves = _ordered_leaves(state)
        assert len(leaves) == len(plans)
        owned = _owned_ranks(mesh, ax)
        rank_values = {r: [None] * len(plans) for r in sorted(owned)}
        for i, (leaf, plan) in enumerate(zip(leaves, plans)):
            vals, _ = _leaf_rank_values(leaf, plan, world, mesh, ax)
            for r, v in vals.items():
                if r in rank_values:
                    rank_values[r][i] = v
    finally:
        # Fires on failure too: a lingering begin would mis-attribute
        # every later hang on this rank as checkpoint-bound.
        _flight.record("checkpoint.extract.done", None, world=world)
    # Every owned rank must hold a host value for every leaf, or the
    # shard file would silently omit a key and the gap would surface
    # only as a restore-time KeyError — after good steps may have been
    # GC'd.  Fail loudly at save time instead.
    for r, vals in rank_values.items():
        missing = [plans[i].spec.path
                   for i, v in enumerate(vals) if v is None]
        if missing:
            raise ValueError(
                f"rank {r}: no host value recovered for leaves "
                f"{missing}; was the state threaded with "
                "zero_state_specs so every local shard is addressable?")
    specs = [p.spec for p in plans]
    return ExtractedState(
        specs=specs, rank_values=rank_values, world=world,
        fingerprint=M.spec_fingerprint(specs),
        mesh_shape={str(a): int(mesh.shape[a]) for a in mesh.axis_names})


def fingerprint_extra(ext: ExtractedState,
                      extra: Optional[dict] = None) -> dict:
    """``extra`` with the run fingerprint stamped — the manifest payload
    both the disk commit and the replica entries carry."""
    extra = dict(extra or {})
    extra[M.RUN_FINGERPRINT_KEY] = {
        "leaf_spec_sha256": ext.fingerprint,
        "mesh_shape": dict(ext.mesh_shape),
        "world_size": ext.world,
    }
    return extra


def save_extracted(root: str, ext: ExtractedState, step: int,
                   keep: Optional[int] = None,
                   extra: Optional[dict] = None) -> M.Manifest:
    """Write one committed step from an already-extracted payload — the
    durable half of :func:`save_zero_state`, also what the async
    committer flushes from its background thread (extraction must
    happen at the commit point; the disk write need not)."""
    # Flight recorder: a rank that stops submitting collectives while
    # inside this call (shard writes, the commit barrier) attributes as
    # checkpoint-bound in a hang report — the begin event with no commit
    # after it is the signal.
    _flight.record("checkpoint.save.begin", root, step=int(step))
    # Run fingerprint: refuse to interleave a DIFFERENT run's steps into
    # this directory (same fingerprint check as restore — a foreign
    # save would poison `latest` resolution for both runs).
    _check_run_fingerprint(root, ext.fingerprint, direction="save")
    extra = fingerprint_extra(ext, extra)

    from ..core.state import global_state
    barrier = None
    committer = True
    if global_state.initialized and global_state.process_count > 1:
        from ..ops import collective as C
        barrier = C.barrier
        committer = global_state.process_rank == 0
    # Chaos drill hook: a scheduled commit-window crash lands between
    # the shard writes and the manifest — the torn-step window the
    # engine's manifest-last protocol (and the replica tier's seal)
    # exists for.
    from ..recovery.chaos import chaos as _chaos

    def _pre_commit():
        _chaos().maybe_crash("pre_manifest", int(step))

    manifest = E.save_leaves(
        root, step, ext.specs, ext.rank_values, ext.world,
        committer=committer, extra=extra, barrier=barrier,
        pre_commit=_pre_commit)
    if keep is not None and committer:
        E.gc_steps(root, keep=keep)
    if barrier is not None:
        # Post-commit barrier: when save_zero_state returns on ANY
        # process, the manifest is durably on disk — callers (e.g. the
        # elastic commit loop) can key decisions off `latest_step`
        # without racing the committer's manifest write.
        barrier()
    _flight.record("checkpoint.save.commit", root, step=int(step))
    return manifest


def save_zero_state(root: str, state, step: int, mesh=None,
                    axis_name: Optional[str] = None,
                    keep: Optional[int] = None,
                    extra: Optional[dict] = None) -> M.Manifest:
    """Write one committed checkpoint step of a pytree containing ZeRO
    state (non-ZeRO leaves ride along as replicated values).

    Single-controller (tests, one-process TPU slices): this call writes
    every rank's shard and commits.  Multi-controller: each process
    writes the shards it can address, a barrier separates shard writes
    from the manifest, and only process 0 commits — the engine's
    write-shards-then-commit protocol.
    """
    ext = extract_zero_state(state, mesh=mesh, axis_name=axis_name)
    return save_extracted(root, ext, step, keep=keep, extra=extra)


def rebuild_restored(restored, like, source: str = "the checkpoint"):
    """Rebuild ``like``'s pytree from an opened step — anything exposing
    ``manifest``, ``full_value(spec)`` and ``padded_full(spec)``:
    ``engine.RestoredStep`` (disk, eager), ``engine.LazyStep`` (disk,
    streaming) or the recovery tier's in-memory reassembly.  One rebuild
    path means a peer restore is bit-identical to the disk restore of
    the same step by construction."""
    import jax.numpy as jnp
    # Cross-run guard: the stamped fingerprint must match the restore
    # target's structure (world-size-invariant, so elastic N→M restores
    # of the same run always pass).
    target_plans, _, _ = _plan_tree(like, restored.manifest.world_size,
                                    validate=False)
    target_fp = M.spec_fingerprint([p.spec for p in target_plans])
    saved_fp = _recorded_fingerprint(restored.manifest)
    if saved_fp != target_fp and not _foreign_allowed():
        raise ValueError(
            f"{source} was written by a different run: "
            f"checkpoint leaf-spec fingerprint {saved_fp[:12]}... != "
            f"restore target's {target_fp[:12]}... (different model/"
            f"optimizer structure, dtypes or sizes).  Refusing the "
            f"cross-run restore: point checkpoint_dir at this run's "
            f"directory, or set HVD_TPU_CKPT_ALLOW_FOREIGN=1 to "
            f"override.")
    plans, groups, outer_def = _plan_tree_like(like, restored.manifest)

    new_leaves: List[Any] = []
    for plan in plans:
        spec = plan.spec
        if spec.kind == M.REPLICATED:
            new_leaves.append(restored.full_value(spec))
        else:
            new_leaves.append(jnp.asarray(restored.padded_full(spec)))
    return _rebuild(groups, outer_def, new_leaves)


def restore_zero_state(root: str, like, mesh=None,
                       axis_name: Optional[str] = None,
                       step: Optional[int] = None,
                       streaming: Optional[bool] = None):
    """Restore the newest committed step (or ``step``) into the structure
    of ``like``, resharded for the current world size.

    ``like`` supplies the pytree structure only (e.g. the pre-failure
    state object, or a fresh ``zero_init``); vector moment leaves come
    back as full padded flat buffers for THIS world — thread them with
    ``zero_state_specs`` and every rank sees exactly its shard, even
    when the checkpoint was written by a different number of ranks.

    ``streaming`` (default ``HVD_TPU_CKPT_STREAMING``, off) reads the
    shard files one LEAF at a time instead of loading every shard up
    front: the restore machinery's transient memory drops from O(total
    state) to O(largest leaf x old world) — the path for states that
    would not fit in host RAM twice.  Bit-identical output either way;
    see docs/checkpointing.md.
    """
    if mesh is None:
        from ..core import basics
        mesh = basics.mesh()
    ax = _default_axis(axis_name)
    world = _axis_world(mesh, ax)
    if streaming is None:
        from ..core.config import Config, get_bool
        streaming = get_bool("CKPT_STREAMING", Config.ckpt_streaming)
    if step is None:
        step = E.latest_step(root)
        if step is None:
            raise FileNotFoundError(
                f"no committed checkpoint step under {root}")
    _flight.record("checkpoint.restore.begin", root, step=int(step),
                   streaming=bool(streaming))
    source = f"step {step} under {root}"
    if streaming:
        with E.open_step(root, step, world) as restored:
            out = rebuild_restored(restored, like, source=source)
    else:
        restored = E.restore_leaves(root, step, world)
        out = rebuild_restored(restored, like, source=source)
    _flight.record("checkpoint.restore.done", root, step=int(step))
    return out


# ---------------------------------------------------------------------------
# Tree rebuild plumbing
# ---------------------------------------------------------------------------

def _ordered_leaves(tree) -> List[Any]:
    """Leaves in the exact order _plan_tree enumerates them."""
    import jax
    outer, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_zero)
    leaves: List[Any] = []
    for _, leaf in outer:
        if _is_zero(leaf):
            leaves.extend(jax.tree_util.tree_leaves(leaf.sizes))
            leaves.extend(jax.tree_util.tree_leaves(leaf.inner))
            if getattr(leaf, "residual", None) is not None:
                leaves.extend(jax.tree_util.tree_leaves(leaf.residual))
        else:
            leaves.append(leaf)
    return leaves


def _plan_tree_like(like, manifest: M.Manifest):
    """Plan with the structure of ``like`` (validate=False: the live
    tree's world — and so its vector leaf shapes — may differ from the
    checkpoint's) but the manifest's authoritative specs."""
    plans, groups, outer_def = _plan_tree(like, manifest.world_size,
                                          validate=False)
    if len(plans) != len(manifest.leaves):
        raise ValueError(
            f"checkpoint at step {manifest.step} has "
            f"{len(manifest.leaves)} leaves but the restore target has "
            f"{len(plans)}; structures must match "
            f"(first checkpoint leaf: {manifest.leaves[0].path})")
    def _full_vector(spec):
        # The flat-vs-dense ambiguity spec_fingerprint canonicalizes
        # (manifest.py): a full 1-D vector classifies SHARDED or
        # REPLICATED depending on the world the target plan was
        # evaluated under.  The saved spec wins below either way.
        return (len(spec.shape) == 1
                and int(spec.shape[0]) == int(spec.true_size))

    for plan, saved in zip(plans, manifest.leaves):
        if plan.spec.kind != saved.kind and not (
                _full_vector(plan.spec) and _full_vector(saved)):
            raise ValueError(
                f"leaf {saved.path}: checkpoint kind {saved.kind} != "
                f"target kind {plan.spec.kind}")
        plan.spec = saved  # restore drives off the manifest's specs
    return plans, groups, outer_def


def _rebuild(groups, outer_def, new_leaves: List[Any]):
    import jax
    ZeroState = _zero_state_type()
    outer_leaves = []
    i = 0
    for kind, count, template in groups:
        vals = new_leaves[i:i + count]
        i += count
        if kind == "plain":
            outer_leaves.append(vals[0])
        else:
            n_sizes = len(jax.tree_util.tree_leaves(template.sizes))
            n_inner = len(jax.tree_util.tree_leaves(template.inner))
            sizes_def = jax.tree_util.tree_structure(template.sizes)
            inner_def = jax.tree_util.tree_structure(template.inner)
            sizes = jax.tree_util.tree_unflatten(sizes_def, vals[:n_sizes])
            inner = jax.tree_util.tree_unflatten(
                inner_def, vals[n_sizes:n_sizes + n_inner])
            kw = {}
            if getattr(template, "residual", None) is not None:
                res_def = jax.tree_util.tree_structure(template.residual)
                kw["residual"] = jax.tree_util.tree_unflatten(
                    res_def, vals[n_sizes + n_inner:])
            outer_leaves.append(ZeroState(inner=inner, sizes=sizes, **kw))
    return jax.tree_util.tree_unflatten(outer_def, outer_leaves)
