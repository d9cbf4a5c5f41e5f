"""Production FSDP: ZeRO-style sharded weight update in the default
fit path.

The replica-style fit loop keeps full params AND full updater state on
every device, so memory — not FLOPs — caps model size.  This module
promotes the 5-axis mesh (parallel/mesh.py) into ``MultiLayerNetwork.fit``
and ``ComputationGraph.fit`` behind ``conf.sharding(data=..., fsdp=...)``:

* params and updater state are laid out by a :class:`ShardingPlan` —
  large weight matrices shard over the ``fsdp`` axis, small arrays
  (biases, BN stats) under ``replicate_below`` elements stay replicated;
* the fused train step is jitted with ``in_shardings``/``out_shardings``
  and ``donate_argnums`` on params+updater so the step is in-place on
  device, and gradients carry an explicit ``with_sharding_constraint``
  to the param layout — XLA lowers that to reduce-scatter(grads) →
  per-shard updater update → all-gather(params), the weight-update
  sharding of "Automatic Cross-Replica Sharding of Weight Update in
  Data-Parallel Training" (arXiv 2004.13336);
* checkpoints stay mesh-shape-tolerant: the canonical flat host vector
  (nn/serialization.py) is the portable redistribution format (the
  single-host analog of arXiv 2112.01075's collective-based resharding),
  and :func:`sharding_manifest` records the mesh + per-param specs so
  ``resume_from_checkpoint`` can reshard host-side onto ANY mesh.

Degrades gracefully: no ``conf.sharding()`` / a single visible device /
an indivisible mesh → :func:`plan_from_conf` returns None and the fit
path is byte-identical to the replica-style one.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import warnings
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.ops import bucketing
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.parallel import mesh as mesh_util

log = logging.getLogger(__name__)

tree_map = jax.tree_util.tree_map

# Mesh construction touches every device — cache per (devices, shape).
_MESH_CACHE: Dict[Tuple, Mesh] = {}


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """Resolved sharding layout for one model: the mesh plus the policy
    mapping each array shape to a :class:`NamedSharding`."""

    mesh: Mesh
    replicate_below: int
    key: Tuple  # identity for trace-token / rebuild decisions

    @property
    def n_data(self) -> int:
        """Batch-axis degree — the data(+fsdp) product every global
        batch must divide into."""
        return self.mesh.shape["data"] * self.mesh.shape["fsdp"]

    def param_sharding(self, shape) -> NamedSharding:
        return mesh_util.param_sharding(
            self.mesh, tuple(shape), replicate_below=self.replicate_below)

    def batch_sharding(self) -> NamedSharding:
        return mesh_util.data_sharded(self.mesh)

    def replicated(self) -> NamedSharding:
        return mesh_util.replicated(self.mesh)

    def tree_shardings(self, tree):
        return tree_map(lambda a: self.param_sharding(a.shape), tree)

    def constrain_grads(self, tree):
        """The explicit ZeRO reduce-scatter point: pin each gradient to
        its param's fsdp layout right after backward, so XLA lowers the
        data-parallel gradient reduction as reduce-scatter into shards
        instead of a full all-reduce, and the updater math that follows
        runs per-shard."""
        return tree_map(
            lambda g: jax.lax.with_sharding_constraint(
                g, self.param_sharding(g.shape)), tree)


def conf_key(g) -> Optional[Tuple]:
    """Trace-token component for the conf's sharding request (None when
    sharding is off) — cheap, no device enumeration."""
    if not getattr(g, "sharding_enabled", False):
        return None
    return (g.sharding_data, g.sharding_fsdp, g.sharding_model,
            g.sharding_replicate_below)


def plan_key(plan: Optional[ShardingPlan]) -> Optional[Tuple]:
    return None if plan is None else plan.key


def plan_from_conf(g, devices=None) -> Optional[ShardingPlan]:
    """Build the active plan for a conf, or None when sharding should
    stay off: not enabled, a single visible device (replica-style is
    already optimal — the graceful-degrade contract), or a mesh request
    the device count cannot satisfy (warned once, never fatal)."""
    if not getattr(g, "sharding_enabled", False):
        return None
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < 2:
        return None
    cfg = mesh_util.MeshConfig(
        data=int(g.sharding_data), fsdp=int(g.sharding_fsdp),
        model=int(g.sharding_model))
    try:
        shape = cfg.resolve(len(devices))
    except ValueError as e:
        warnings.warn(f"conf.sharding() disabled: {e} — training "
                      f"replica-style", stacklevel=2)
        return None
    cache_key = (tuple(id(d) for d in devices), shape)
    mesh = _MESH_CACHE.get(cache_key)
    if mesh is None:
        mesh = Mesh(np.asarray(devices).reshape(shape), mesh_util.AXES)
        _MESH_CACHE[cache_key] = mesh
    rb = max(0, int(getattr(g, "sharding_replicate_below", 0)))
    return ShardingPlan(mesh=mesh, replicate_below=rb,
                        key=(shape, rb, cache_key[0]))


def plan_from_mesh(mesh: Mesh, replicate_below: int = 0) -> ShardingPlan:
    """Wrap an explicit mesh (ParallelWrapper's constructor argument)
    in the same plan machinery the conf-driven path uses."""
    shape = tuple(mesh.shape[a] for a in mesh_util.AXES)
    devs = tuple(id(d) for d in mesh.devices.flat)
    return ShardingPlan(mesh=mesh, replicate_below=int(replicate_below),
                        key=(shape, int(replicate_below), devs))


# --------------------------------------------------------------------------
# The sharded step
# --------------------------------------------------------------------------

def _spans_devices(tree) -> bool:
    """Does any leaf live on more than one device?  (numpy leaves and
    uncommitted arrays have no multi-device sharding.)"""
    shardings = (getattr(a, "sharding", None)
                 for a in jax.tree_util.tree_leaves(tree))
    return any(sh is not None and len(sh.device_set) > 1
               for sh in shardings)


def partitioned_if_sharded(model, raw):
    """``raw``, traced under pallas_kernels.partitioned_trace() when
    GSPMD will partition it: the model has a plan, or its params already
    live on more than one device (a ParallelWrapper-trained model keeps
    its mesh-committed params after the wrapper's plan is gone).  The
    engines build their raw step (per-step and fused scan), score and
    output functions through this, so the fused Mosaic tiers (not
    partitionable) stay out of those traces."""
    if getattr(model, "_sharding_plan", None) is None \
            and not _spans_devices(model.net_params):
        return raw

    @functools.wraps(raw)
    def traced(*args):
        with pk.partitioned_trace():
            return raw(*args)
    return traced


def jit_sharded_step(raw_step, plan: ShardingPlan, params, opts):
    """pjit the engines' raw train step with the plan's layouts:
    params/updater sharded (fsdp/model/expert), carried state, score,
    key and iteration replicated, the batch sharded over data(+fsdp),
    and params+state+updater donated so the step updates buffers in
    place on device.

    net_state uses a PREFIX sharding (one spec for the whole subtree):
    an RNN step's output state gains carried keys the input structure
    doesn't have, so a full-tree spec would pin the wrong structure for
    out_shardings."""
    param_sh = plan.tree_shardings(params)
    opt_sh = plan.tree_shardings(opts)
    repl = plan.replicated()
    batch_sh = plan.batch_sharding()
    return jax.jit(
        raw_step,
        in_shardings=(param_sh, repl, opt_sh, batch_sh, batch_sh,
                      None, None, repl, repl),
        out_shardings=(param_sh, repl, opt_sh, repl, repl, repl),
        donate_argnums=(0, 1, 2))


def jit_sharded_output(raw_out, plan: ShardingPlan, params):
    """pjit the engines' raw inference fn for sharded SERVING (ROADMAP
    3a): params keep the plan's fsdp/model layout (a model that only
    fits sharded never materializes whole on one device), carried state
    stays replicated, the batch shards over data(+fsdp), and the output
    is replicated — XLA all-gathers the result over ICI inside the
    program, so the response edge does exactly ONE explicit host gather
    (``jax.device_get``) instead of pulling per-device shards."""
    param_sh = plan.tree_shardings(params)
    repl = plan.replicated()
    batch_sh = plan.batch_sharding()
    return jax.jit(raw_out,
                   in_shardings=(param_sh, repl, batch_sh, batch_sh),
                   out_shardings=repl)


def pad_inference_rows(x, mask, n_data: int):
    """Zero-pad a host inference batch (rows plus its optional mask) up
    to a multiple of the mesh's batch degree so the data-sharded layout
    divides evenly.  Inference rows are independent — no batch
    statistics — so zero rows are exact and the caller just slices the
    output back to ``n``.  Returns ``(x, mask, n)`` with ``n`` the real
    row count (``None`` when no padding was needed)."""
    x = np.asarray(x)
    n = int(x.shape[0])
    rem = n % max(1, int(n_data))
    if rem == 0:
        return x, mask, None
    pad = [(0, n_data - rem)] + [(0, 0)] * (x.ndim - 1)
    x = np.pad(x, pad)
    if mask is not None:
        m = np.asarray(mask)
        m = np.pad(m, [(0, n_data - rem)] + [(0, 0)] * (m.ndim - 1))
        mask = m
    return x, mask, n


def place_model(plan: ShardingPlan, model) -> None:
    """Move a model's param/updater/state pytrees onto the mesh with the
    plan's layouts (host→device scatter; re-placing already-placed
    arrays is a no-op per leaf).  Also refreshes the sharding gauges."""
    with monitor.span("sharding/place", phase="device_put"):
        if model.net_params is not None:
            model.net_params = jax.device_put(
                model.net_params, plan.tree_shardings(model.net_params))
        if model.opt_states is not None:
            model.opt_states = jax.device_put(
                model.opt_states, plan.tree_shardings(model.opt_states))
        if model.net_state is not None:
            repl = plan.replicated()
            model.net_state = jax.device_put(
                model.net_state,
                tree_map(lambda a: repl, model.net_state))
    record_gauges(plan, model)


def shard_put(plan: ShardingPlan, host_batch):
    """Place one normalized host batch (any pytree of arrays; None
    leaves pass through) onto the mesh, batch-dim sharded.  Multi-process
    (scaleout tier): each host contributes its process-local rows."""
    batch_sh = plan.batch_sharding()

    def put(a):
        arr = np.asarray(a)
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(batch_sh, arr)
        return jax.device_put(arr, batch_sh)

    return tree_map(put, host_batch)


def stack_for_scan(plan: ShardingPlan, host_batches):
    """Stack K same-shape host batches along a leading scan axis and
    place them with the scan-aware sharding P(None, ('data','fsdp')) —
    the fused-steps (lax.scan) input layout."""
    scan_sh = NamedSharding(plan.mesh, P(None, ("data", "fsdp")))

    def put(*leaves):
        arr = np.stack([np.asarray(l) for l in leaves])
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(scan_sh, arr)
        return jax.device_put(arr, scan_sh)

    return tree_map(put, *host_batches)


# --------------------------------------------------------------------------
# Batch normalization (pad-or-trim to the data degree) — shared by the
# engines' sharded fit path and ParallelWrapper
# --------------------------------------------------------------------------

def normalize_batch(model, ds, n_data: int, is_graph: bool, owner=None):
    """(x, y, fm, lm) host pytrees at a data-degree multiple, or None
    when everything would be dropped.  A non-divisible batch is PADDED
    with cycled real rows whose loss is masked out and the valid rows'
    mask rescaled, so every example trains and gradients equal the
    unsharded step exactly (the reference's round-robin feedDataSet
    trains on every example — ref: parallelism/ParallelWrapper.java:383).
    Mask-nonlinear losses fall back to trimming (warned once on
    ``owner``).  Returns ``(batch, n, bucket)`` with ``n`` the REAL
    example count and ``bucket`` the shape bucket when the conf's shape
    bucketing subsumed the remainder policy."""
    from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
    owner = owner if owner is not None else model
    if is_graph and isinstance(ds, DataSet):
        # ComputationGraph steps take TUPLES of inputs/labels
        ds = MultiDataSet([ds.features], [ds.labels],
                          [ds.features_mask], [ds.labels_mask])
    n = ds.num_examples()
    g = model.conf.global_conf
    pad_supported = bucketing.pad_supported(model)
    if getattr(g, "shape_bucketing", False) and pad_supported:
        # shape bucketing subsumes the remainder policy: the batch
        # bucket is lifted to a data-degree multiple, rows are cycled
        # and the labels mask rescaled exactly as below — every sharded
        # launch is then bucket-shaped, so the jitted sharded step (and
        # the fused scan) compiles once per bucket
        fn = (bucketing.bucket_train_multidataset
              if isinstance(ds, MultiDataSet)
              else bucketing.bucket_train_dataset)
        ds_b, bucket = fn(ds, g, min_multiple=n_data)
        if bucket is not None:
            return host_batch(ds_b), n, bucket
    rem = n % n_data
    pad_ok = bool(rem) and pad_supported
    lm_base = None
    if pad_ok:
        # The synthesized labels mask takes precedence over the
        # features-propagated time mask in the step's loss (the engines'
        # loss_fn lm resolution), so when a features mask exists without
        # a labels mask it must BECOME the base of the scaled mask — and
        # only when its shape provably matches the labels' time layout;
        # otherwise trim.
        if isinstance(ds, MultiDataSet):
            # container-level None checks are not enough: the
            # DataSet→MultiDataSet wrap above produces [None] lists, so
            # compare the ENTRIES
            def _all_none(t):
                return t is None or all(m is None for m in t)
            if not _all_none(ds.features_masks) \
                    and _all_none(ds.labels_masks):
                pad_ok = False  # multi-input→output mask routing is
                # ambiguous; don't guess
        elif ds.labels_mask is not None:
            lm_base = np.asarray(ds.labels_mask)
        elif ds.features_mask is not None:
            fm_arr = np.asarray(ds.features_mask)
            y_arr = np.asarray(ds.labels)
            if fm_arr.ndim == y_arr.ndim - 1 \
                    and fm_arr.shape == y_arr.shape[:-1]:
                lm_base = fm_arr
            else:
                pad_ok = False
    if pad_ok:
        target = n + (n_data - rem)
        cyc = lambda a: (None if a is None  # noqa: E731
                         else bucketing.cycle_rows(a, target))
        if isinstance(ds, MultiDataSet):
            lms = (ds.labels_masks
                   if ds.labels_masks is not None
                   else (None,) * len(ds.labels))
            return ((tuple(cyc(a) for a in ds.features),
                     tuple(cyc(a) for a in ds.labels),
                     None if ds.features_masks is None else
                     tuple(cyc(a) for a in ds.features_masks),
                     tuple(bucketing.scaled_mask(lm, y, n, target)
                           for lm, y in zip(lms, ds.labels))), n, None)
        return ((cyc(ds.features), cyc(ds.labels),
                 cyc(ds.features_mask),
                 bucketing.scaled_mask(lm_base, ds.labels,
                                       n, target)), n, None)
    if rem:
        n_new = (n // n_data) * n_data
        _warn_remainder(owner, n - n_new, n, n_data)
        n = n_new
        if n == 0:
            return None
    if isinstance(ds, MultiDataSet):
        trim = lambda arrs: (  # noqa: E731
            None if arrs is None else tuple(
                None if a is None else np.asarray(a)[:n] for a in arrs))
        return ((trim(ds.features), trim(ds.labels),
                 trim(ds.features_masks), trim(ds.labels_masks)), n, None)
    return ((np.asarray(ds.features)[:n], np.asarray(ds.labels)[:n],
             None if ds.features_mask is None
             else np.asarray(ds.features_mask)[:n],
             None if ds.labels_mask is None
             else np.asarray(ds.labels_mask)[:n]), n, None)


def host_batch(ds):
    """DataSet/MultiDataSet → the (x, y, fm, lm) host-pytree the sharded
    step consumes."""
    from deeplearning4j_tpu.datasets.dataset import MultiDataSet
    if isinstance(ds, MultiDataSet):
        tup = lambda arrs: (  # noqa: E731
            None if arrs is None else tuple(
                None if a is None else np.asarray(a) for a in arrs))
        return (tuple(np.asarray(a) for a in ds.features),
                tuple(np.asarray(a) for a in ds.labels),
                tup(ds.features_masks), tup(ds.labels_masks))
    return (np.asarray(ds.features), np.asarray(ds.labels),
            None if ds.features_mask is None
            else np.asarray(ds.features_mask),
            None if ds.labels_mask is None
            else np.asarray(ds.labels_mask))


def _warn_remainder(owner, dropped: int, batch: int, n_data: int) -> None:
    """Non-divisible batches are normally padded+masked so every example
    trains; this warning only fires on the trim fallback for
    mask-nonlinear losses (bucketing.MASK_NONLINEAR_LOSSES /
    CenterLoss)."""
    if not getattr(owner, "_remainder_warned", False):
        owner._remainder_warned = True
        warnings.warn(
            f"sharded fit: dropping {dropped} of {batch} examples per "
            f"batch (batch not divisible by data degree {n_data}); pad "
            f"or resize batches to avoid this",
            stacklevel=4)


# --------------------------------------------------------------------------
# Observability: dl4j_sharding_* gauges (docs/OBSERVABILITY.md)
# --------------------------------------------------------------------------

def _tree_bytes(tree, plan: Optional[ShardingPlan]):
    """(total_bytes, per_device_bytes, n_sharded, n_replicated) for one
    pytree under ``plan`` (per-device = replica bytes when plan None).
    Uses each ARRAY's actual committed sharding when available so the
    gauges report reality, not intent."""
    total = per_dev = 0
    sharded = replicated = 0
    for a in jax.tree_util.tree_leaves(tree):
        shape = tuple(a.shape)
        nbytes = int(np.prod(shape) or 1) * np.dtype(a.dtype).itemsize
        total += nbytes
        sh = getattr(a, "sharding", None)
        if sh is None and plan is not None:
            sh = plan.param_sharding(shape)
        if sh is None:
            per_dev += nbytes
            replicated += 1
            continue
        try:
            shard_shape = sh.shard_shape(shape)
        except Exception:
            shard_shape = shape
        shard_bytes = int(np.prod(shard_shape) or 1) * \
            np.dtype(a.dtype).itemsize
        per_dev += shard_bytes
        if shard_bytes < nbytes:
            sharded += 1
        else:
            replicated += 1
    return total, per_dev, sharded, replicated


def record_gauges(plan: ShardingPlan, model) -> None:
    """Publish the sharding family: mesh shape per axis, params/updater
    bytes total and per device, sharded/replicated param counts, and the
    per-step collective-traffic estimates (all-gather = full bytes of
    every fsdp-sharded param gathered for the forward; reduce-scatter =
    the same bytes of gradients scattered into shards)."""
    reg = monitor.get_registry()
    for ax in mesh_util.AXES:
        reg.gauge("dl4j_sharding_mesh_devices",
                  "active sharding mesh size along each named axis",
                  labels=("axis",)).labels(axis=ax).set(plan.mesh.shape[ax])
    p_total, p_dev, p_sh, p_rep = _tree_bytes(model.net_params, plan)
    o_total, o_dev, _, _ = _tree_bytes(model.opt_states, plan)
    reg.gauge("dl4j_sharding_param_bytes_total",
              "model parameter bytes (unsharded logical size)").set(p_total)
    reg.gauge("dl4j_sharding_param_bytes_per_device",
              "model parameter bytes resident per device").set(p_dev)
    reg.gauge("dl4j_sharding_updater_bytes_total",
              "updater-state bytes (unsharded logical size)").set(o_total)
    reg.gauge("dl4j_sharding_updater_bytes_per_device",
              "updater-state bytes resident per device").set(o_dev)
    reg.gauge("dl4j_sharding_params_sharded",
              "param arrays sharded over the mesh").set(p_sh)
    reg.gauge("dl4j_sharding_params_replicated",
              "param arrays replicated (below the size threshold or "
              "indivisible)").set(p_rep)
    # per-step collective traffic estimate: every byte a param is short
    # of its full size must be all-gathered for the forward, and the
    # matching gradient bytes reduce-scattered after backward
    collective = max(0, p_total - p_dev)
    reg.gauge("dl4j_sharding_allgather_bytes_per_step",
              "estimated param bytes all-gathered per train step").set(
                  collective)
    reg.gauge("dl4j_sharding_reducescatter_bytes_per_step",
              "estimated gradient bytes reduce-scattered per train "
              "step").set(collective)


# --------------------------------------------------------------------------
# Mesh-reshape-tolerant checkpoints (manifest metadata + reshard logging)
# --------------------------------------------------------------------------

def sharding_manifest(model) -> Optional[dict]:
    """Serializable description of a model's active mesh + per-param
    shardings for the checkpoint manifest — None for replica-style
    models (the serde-compatible default: absent/None means
    'replicated everywhere', which is exactly what PR-5-era manifests
    implied)."""
    plan = getattr(model, "_sharding_plan", None)
    if plan is None:
        return None
    mesh_axes = {ax: int(plan.mesh.shape[ax]) for ax in mesh_util.AXES}
    specs = {}
    try:
        for key, arr in model.param_table().items():
            sh = getattr(arr, "sharding", None)
            spec = getattr(sh, "spec", None)
            if spec is None:
                spec = plan.param_sharding(arr.shape).spec
            specs[key] = [list(p) if isinstance(p, tuple) else p
                          for p in tuple(spec)]
    except Exception:  # never let metadata break a checkpoint save
        specs = {}
    return {"mesh": mesh_axes, "replicate_below": plan.replicate_below,
            "n_devices": int(np.prod(list(mesh_axes.values()))),
            "params": specs}


def note_reshard(model, saved_sharding: Optional[dict]) -> None:
    """Called by resume when a checkpoint's recorded mesh differs from
    the restoring model's: the flat host vector was already
    redistributed by ``set_params`` (host-side gather → scatter, the
    portable-collectives analog on one host); here we log and count it
    so cross-mesh restores are visible in /metrics."""
    cur = sharding_manifest(model)
    saved_mesh = (saved_sharding or {}).get("mesh")
    cur_mesh = (cur or {}).get("mesh")
    if saved_mesh == cur_mesh:
        return
    monitor.get_registry().counter(
        "dl4j_sharding_reshard_total",
        "checkpoint restores that redistributed params across a "
        "different mesh than they were saved on").inc()
    log.info("resharded checkpoint: saved mesh %s -> restored mesh %s",
             saved_mesh or "replicated", cur_mesh or "replicated")
