"""Data-parallel gradient synchronization.

Capability port of apex.parallel.DistributedDataParallel + Reducer
(reference: apex/parallel/distributed.py:89-639). The reference's machinery —
per-param backward hooks, greedy bucket assembly, rank-0 bucket-structure
broadcast, multi-stream flatten/allreduce/unflatten overlap — exists to hide
NCCL latency behind eager-mode backward. Under XLA none of that is manual:
gradients live in one jitted computation, ``psum`` over a mesh axis is an
async collective the latency-hiding scheduler overlaps with the remaining
backward automatically, and "buckets" are XLA's collective-combining pass.

What survives as *semantics* (and is preserved here):
  * gradient averaging over the data-parallel group (``gradient_average``)
  * ``allreduce_always_fp32`` — upcast before the reduction
  * ``gradient_predivide_factor`` — divide by f before, world/f after
    (distributed.py:148-175)
  * param broadcast at init → ``broadcast_params`` (distributed.py:253)
Bucket/stream knobs are accepted and ignored (documented no-ops).

Use inside ``shard_map``/``pmap`` over a mesh with a data axis; under plain
``pjit`` with sharded batches XLA inserts the same psum from the loss mean.

NOTE on shard_map's varying-type system (jax >= 0.8): differentiating wrt a
*replicated* (invariant) param auto-inserts the cross-replica psum — grads
arrive already summed, and calling ``average_gradients`` on them would
double-count. The apex-DDP model (each replica owns a param copy, grads
reduced explicitly) corresponds to *varying* params: apply
``jax.lax.pvary(params, axis_name)`` before the local grad, then
``average_gradients``. ``broadcast_params`` returns varying params.
"""

import math
import warnings

import jax
import jax.numpy as jnp

from apex_tpu.parallel import collectives


def pvary(x, axis_name):
    """invariant → varying cast (per-replica ownership)."""
    return jax.lax.pcast(x, axis_name, to="varying")


def allreduce_gradients(grads, axis_name="data", gradient_average=True,
                        allreduce_always_fp32=False,
                        gradient_predivide_factor=1.0, *,
                        compress=None, hierarchical=None, ef_state=None):
    """All-reduce (mean) a gradient pytree over ``axis_name`` (a mesh
    axis name, or a declared ``(inner, outer)`` pair for hierarchical
    reduction).

    The functional core of DDP (reference hot path:
    apex/parallel/distributed.py:425-475 allreduce_bucket →
    allreduce_maybe_retain). One psum per dtype-group; XLA combines and
    overlaps.

    Scale-out knobs (``apex_tpu.parallel.collectives``): ``compress``
    (per-call scheme, raises on unknown; None consults
    ``set_grad_compress``/``APEX_GRAD_COMPRESS``) and ``hierarchical``
    (per-call, raises over an unfactored axis; None consults
    ``set_hier_allreduce``/``APEX_HIER_ALLREDUCE``). With both
    resolved off the jaxpr is byte-identical to the pre-collectives
    psum path. ``ef_state`` threads the error-feedback residual
    (``collectives.ef_init``): when it is not None the return value
    is ``(grads, new_ef_state)`` instead of ``grads`` — compensation
    is state the caller carries across steps, not a side effect."""
    axes = collectives.axes_tuple(axis_name)
    nelems = sum(math.prod(g.shape) for g in
                 jax.tree_util.tree_leaves(grads))
    scheme = collectives.resolve_compress(compress, nelems=nelems)
    hier = collectives.resolve_hier(hierarchical, axes, nelems=nelems)
    if scheme is None and not hier:
        axis = axes if len(axes) > 1 else axes[0]
        world = jax.lax.psum(1, axis)

        def reduce_one(g):
            orig = g.dtype
            if allreduce_always_fp32:
                g = g.astype(jnp.float32)
            if gradient_predivide_factor != 1.0:
                g = g / gradient_predivide_factor
            g = jax.lax.psum(g, axis)
            if gradient_average:
                post = world / gradient_predivide_factor if gradient_predivide_factor != 1.0 else world
                g = g / post
            elif gradient_predivide_factor != 1.0:
                g = g * gradient_predivide_factor
            return g.astype(orig) if allreduce_always_fp32 else g

        reduced = jax.tree_util.tree_map(reduce_one, grads)
        return reduced if ef_state is None else (reduced, ef_state)

    # compressed / hierarchical route: the collectives layer works on
    # one flat fp32 buffer (allreduce_always_fp32 is trivially
    # satisfied); predivide still happens BEFORE the payload is built
    # (its job is dynamic-range protection, which quantization cares
    # about more, not less)
    pre = gradient_predivide_factor if gradient_predivide_factor != 1.0 \
        else None
    scaled = grads if pre is None else jax.tree_util.tree_map(
        lambda g: g / pre, grads)
    reduced, new_ef = collectives.allreduce_tree(
        scaled, axes, mean=False,
        compress=scheme if scheme is not None else False,
        hierarchical=hier, ef_state=ef_state)
    world = collectives.axes_size(axes)
    if gradient_average:
        post = world / pre if pre is not None else world
        reduced = jax.tree_util.tree_map(lambda g: (g / post).astype(
            g.dtype), reduced)
    elif pre is not None:
        reduced = jax.tree_util.tree_map(lambda g: (g * pre).astype(
            g.dtype), reduced)
    return reduced if ef_state is None else (reduced, new_ef)


def broadcast_params(params, axis_name="data", src_index=0):
    """Make params identical across the axis by broadcasting rank 0's copy
    (reference: flat_dist_call broadcast at distributed.py:253,296)."""

    def bcast(p):
        idx = jax.lax.axis_index(axis_name)
        masked = jnp.where(idx == src_index, p, jnp.zeros_like(p))
        # psum yields an *invariant* (replicated-type) value; re-pvary so the
        # result keeps DDP's per-replica ownership semantics — otherwise
        # later grads wrt it would be auto-psum'd by shard_map's type system
        # and an explicit average_gradients would double-count.
        return pvary(jax.lax.psum(masked, axis_name), axis_name)

    return jax.tree_util.tree_map(bcast, params)


# The accepted-but-inert ctor knobs: eager-NCCL stream/bucketing
# artifacts with no TPU counterpart (XLA's collective combiner and
# async scheduler subsume them). This tuple is the CODE side of the
# documented-no-op audit — docs/API.md's "Accepted-but-inert knobs"
# table must list exactly these (tests/test_noop_knob_audit.py).
NOOP_KNOBS = ("message_size", "delay_allreduce", "num_allreduce_streams",
              "retain_allreduce_buffers", "allreduce_trigger_params",
              "allreduce_communicators", "gradient_average_split_factor",
              "prof")


class DistributedDataParallel:
    """Stateless config object mirroring the reference ctor
    (apex/parallel/distributed.py:129-175); call ``average_gradients``
    inside your shard_map'd step.

    The :data:`NOOP_KNOBS` ctor arguments are eager-NCCL artifacts —
    accepted, warned once on a non-default value, ignored (XLA's
    collective combiner and async scheduler subsume them).
    """

    def __init__(self, module=None, message_size=10000000,
                 delay_allreduce=False, shared_param=None,
                 allreduce_trigger_params=None, retain_allreduce_buffers=False,
                 allreduce_always_fp32=False, num_allreduce_streams=1,
                 allreduce_communicators=None, gradient_average=True,
                 gradient_predivide_factor=1.0, gradient_average_split_factor=None,
                 prof=False, axis_name="data", compress=None,
                 hierarchical=None, overlap_grad=None,
                 overlap_buckets=None):
        if shared_param is not None:
            raise ValueError(
                "shared_param is no longer supported as an option.")
        self.module = module
        self.axis_name = axis_name
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = gradient_predivide_factor
        # per-call knob semantics at ctor time (explicit request ≠
        # preference): an unknown scheme / unfactored hierarchical
        # demand raises HERE, not mid-trace
        self.compress = compress
        self.hierarchical = hierarchical
        collectives.resolve_compress(compress)
        if hierarchical:
            collectives.resolve_hier(
                hierarchical, collectives.axes_tuple(axis_name))
        # overlap knobs (ISSUE 14, apex_tpu.overlap — the one home):
        # the in-backward bucket-interleaved reduction is the TPU
        # rebirth of the reference DDP's per-bucket backward hooks.
        # Ctor values are per-call demands (unknown mode / bad count
        # raise HERE); None defers to setter > env > dispatch table.
        # They shape value_and_grad() only — average_gradients stays
        # the terminal reduction whatever the knobs say, because grads
        # handed in post-backward have no backward left to hide under.
        from apex_tpu import overlap as overlap_mod

        self.overlap_grad = overlap_grad
        self.overlap_buckets = overlap_buckets
        overlap_mod.resolve_grad_overlap(overlap_grad)
        if overlap_buckets is not None:
            overlap_mod.resolve_buckets(overlap_buckets)
        for name, val, default in (
            ("message_size", message_size, 10000000),
            ("delay_allreduce", delay_allreduce, False),
            ("num_allreduce_streams", num_allreduce_streams, 1),
            ("retain_allreduce_buffers", retain_allreduce_buffers, False),
            ("allreduce_trigger_params", allreduce_trigger_params, None),
            ("allreduce_communicators", allreduce_communicators, None),
            ("gradient_average_split_factor",
             gradient_average_split_factor, None),
            ("prof", prof, False),
        ):
            if val != default:
                warnings.warn(
                    f"apex_tpu DDP: `{name}` is a CUDA-stream/bucketing knob "
                    "with no TPU counterpart — XLA handles collective "
                    "combining and overlap; option ignored.")

    def average_gradients(self, grads, ef_state=None):
        return allreduce_gradients(
            grads, self.axis_name,
            gradient_average=self.gradient_average,
            allreduce_always_fp32=self.allreduce_always_fp32,
            gradient_predivide_factor=self.gradient_predivide_factor,
            compress=self.compress, hierarchical=self.hierarchical,
            ef_state=ef_state)

    def value_and_grad(self, loss_fn):
        """``fn(params, *args) -> (loss, reduced_grads)`` under this
        config's resolved overlap schedule
        (``apex_tpu.overlap.bucketed_value_and_grad``): with the knobs
        off, the exact historical program — ``jax.value_and_grad``
        then one terminal :func:`allreduce_gradients` (byte-identical
        jaxpr); with ``overlap_grad="bucketed"`` (ctor demand, or the
        ``APEX_OVERLAP_GRAD`` preference), each layer-group bucket's
        collective is issued inside the backward as its cotangents
        complete — the reference's per-bucket backward hooks
        (apex/parallel/distributed.py:425-475), scheduled at the jaxpr
        level (``costs.collective_schedule``). Call inside your
        shard_map'd step; do NOT also call :meth:`average_gradients`
        on the result (the grads come back reduced)."""
        from apex_tpu.overlap import bucketed_value_and_grad

        return bucketed_value_and_grad(
            loss_fn, self.axis_name, overlap=self.overlap_grad,
            buckets=self.overlap_buckets,
            gradient_average=self.gradient_average,
            allreduce_always_fp32=self.allreduce_always_fp32,
            gradient_predivide_factor=self.gradient_predivide_factor,
            compress=self.compress, hierarchical=self.hierarchical)

    def init_ef_state(self, grads):
        """Zero error-feedback residual for ``average_gradients``
        under this config's resolved knobs (None when compression is
        off). Call inside shard_map; thread the returned state through
        your step."""
        return collectives.ef_init(
            grads, self.axis_name, compress=self.compress,
            hierarchical=self.hierarchical)

    def broadcast_params(self, params):
        return broadcast_params(params, self.axis_name)

    def __call__(self, *args, **kwargs):
        if self.module is None:
            raise ValueError("DistributedDataParallel was built without a module")
        return self.module(*args, **kwargs)


class Reducer:
    """Manual, user-triggered grad reduction (reference:
    apex/parallel/distributed.py:89-126 — for delayed/periodic allreduce)."""

    def __init__(self, module_or_grads_list=None, axis_name="data"):
        self.axis_name = axis_name
        self.module = module_or_grads_list

    def reduce(self, grads):
        return allreduce_gradients(grads, self.axis_name)
