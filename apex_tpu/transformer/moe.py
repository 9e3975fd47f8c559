"""Expert-parallel mixture-of-experts MLP (Switch/top-k routing).

The reference exposes MoE only as a config surface (testing/arguments.py
--num-experts); the capability itself lives outside apex. Here it is a
first-class TPU component, because expert parallelism shapes the mesh
design the same way tp/pp do (SURVEY §2.8 scope note):

  * routing (Switch Transformer style): fp32 router softmax, top-1 or
    top-2 gating, static per-expert ``capacity`` (ceil(tokens/E · factor))
    so every shape is static under jit — dropped tokens pass through the
    residual, exactly the Switch semantics;
  * dispatch/combine are einsums against a [tokens, experts, capacity]
    one-hot — MXU-friendly, no scatter;
  * expert parallelism: experts sharded over the ``ep`` mesh axis; token
    slices travel rank→expert and back via ONE ``lax.all_to_all`` pair
    (the ICI-native analog of the NCCL all-to-all an expert-parallel
    GPU stack hand-writes); gradients ride AD through the collective.

Parity is tested against a single-device reference on the CPU mesh
(tests/test_moe.py) and the ep path is exercised by the driver dryrun.
"""

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import lax


def switch_routing(router_logits, num_experts, capacity, num_selected=1):
    """Top-k routing with static capacity.

    Args:
      router_logits: [T, E] (any float dtype; softmax in fp32).
      capacity: max tokens per expert (static).
      num_selected: 1 (Switch) or 2 (top-2 gating).

    Returns (dispatch [T, E, C] float, combine [T, E, C] float): one-hot
    dispatch mask and probability-weighted combine weights. Tokens beyond
    an expert's capacity are dropped (all-zero rows).
    """
    T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)

    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    remaining = probs
    # running per-expert occupancy across the k selection rounds
    base_count = jnp.zeros((E,), jnp.int32)
    for _ in range(num_selected):
        expert_idx = jnp.argmax(remaining, axis=-1)  # [T]
        onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)  # [T, E]
        # position of each token within its expert (first-come order)
        pos = (jnp.cumsum(onehot, axis=0) - 1 + base_count[None, :])
        pos = jnp.sum(pos * onehot, axis=-1)  # [T]
        keep = pos < capacity
        gate = jnp.sum(probs * onehot, axis=-1) * keep  # [T]
        slot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # [T, C]
        d = onehot.astype(jnp.float32)[:, :, None] * slot[:, None, :]
        d = d * keep[:, None, None]
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
        base_count = base_count + jnp.sum(onehot, axis=0)
        remaining = remaining * (1.0 - onehot)  # mask the chosen expert
    return dispatch, combine


def load_balancing_loss(router_logits, dispatch):
    """Switch aux loss: E · Σ_e f_e · p_e (fraction routed × mean prob)."""
    T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    frac = jnp.sum(dispatch, axis=(0, 2)) / jnp.maximum(
        jnp.sum(dispatch), 1.0)
    mean_prob = jnp.mean(probs, axis=0)
    return E * jnp.sum(frac * mean_prob)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    hidden_size: int
    ffn_hidden_size: int
    num_experts: int
    capacity_factor: float = 1.25
    num_selected: int = 1
    expert_parallel_axis: Optional[str] = None  # "ep" mesh axis or None
    # tensor parallelism WITHIN each expert: the ffn dim is column/row
    # sharded over this axis (same scheme as ParallelMLP) so tp ranks split
    # each expert's weights and FLOPs instead of replicating them
    tensor_parallel_axis: Optional[str] = None
    params_dtype: Any = jnp.float32
    init_method_std: float = 0.02


def collect_moe_aux(intermediates):
    """Sum every sown ``load_balancing_loss`` in an ``intermediates``
    collection (as returned by ``model.apply(..,
    mutable=['intermediates'])``). Trainers add ``coeff * collect_moe_aux``
    to the objective — the Switch aux loss is an explicit loss term, not a
    side effect."""
    total = jnp.zeros((), jnp.float32)
    for path, leaf in jax.tree_util.tree_flatten_with_path(intermediates)[0]:
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if any(n == "load_balancing_loss" for n in names):
            total = total + jnp.sum(leaf)
    return total


class ExpertParallelMLP(nn.Module):
    """MoE FFN block: route → all_to_all → expert MLPs → all_to_all back.

    Input/output [T, h] (callers flatten [s, b, h]). With
    ``expert_parallel_axis`` set, this rank holds num_experts/ep experts
    and runs inside shard_map; without it, all experts are local (the
    single-device reference).
    """

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        T, H = x.shape
        E = cfg.num_experts
        F = cfg.ffn_hidden_size
        ep = 1
        if cfg.expert_parallel_axis is not None:
            ep = lax.axis_size(cfg.expert_parallel_axis)
        tp = 1
        if cfg.tensor_parallel_axis is not None:
            tp = lax.axis_size(cfg.tensor_parallel_axis)
        assert E % ep == 0, f"num_experts {E} not divisible by ep {ep}"
        assert F % tp == 0, f"ffn_hidden_size {F} not divisible by tp {tp}"
        e_loc = E // ep
        f_loc = F // tp
        capacity = int(np.ceil(T * cfg.capacity_factor * cfg.num_selected
                               / E))

        router = nn.Dense(E, use_bias=False, name="router",
                          param_dtype=jnp.float32,
                          kernel_init=nn.initializers.normal(
                              cfg.init_method_std))
        logits = router(x.astype(jnp.float32))
        dispatch, combine = switch_routing(logits, E, capacity,
                                           cfg.num_selected)
        aux = load_balancing_loss(logits, dispatch)
        self.sow("intermediates", "load_balancing_loss", aux)

        # [T, E, C] x [T, H] -> [E, C, H]
        expert_in = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), x)

        # expert weights: this rank's e_loc experts, each expert's ffn dim
        # column/row-sharded over tp. Rank-consistent sharded init
        # (generate the full [E, H, F] tensor, slice this rank's experts
        # and ffn columns) so ranks hold DISTINCT shards matching the
        # unsharded reference — same scheme as tensor_parallel.layers.
        base_init = nn.initializers.normal(cfg.init_method_std)

        def sliced_init(full_shape, e_axis, f_axis):
            def init(key, local_shape, dtype):
                master = base_init(key, full_shape, dtype)
                if ep > 1:
                    idx = lax.axis_index(cfg.expert_parallel_axis)
                    master = lax.dynamic_slice_in_dim(
                        master, idx * e_loc, e_loc, axis=e_axis)
                if tp > 1:
                    idx = lax.axis_index(cfg.tensor_parallel_axis)
                    master = lax.dynamic_slice_in_dim(
                        master, idx * f_loc, f_loc, axis=f_axis)
                return master
            return init

        w1 = self.param("wi", sliced_init((E, H, F), 0, 2),
                        (e_loc, H, f_loc), cfg.params_dtype)
        w2 = self.param("wo", sliced_init((E, F, H), 0, 1),
                        (e_loc, f_loc, H), cfg.params_dtype)

        if ep > 1:
            # [E, C, H] = [ep, e_loc, C, H]: slice j goes to rank j; each
            # rank re-stacks the ep incoming slices along capacity
            send = expert_in.reshape(ep, e_loc, capacity, H)
            recv = lax.all_to_all(send, cfg.expert_parallel_axis,
                                  split_axis=0, concat_axis=0, tiled=False)
            # [ep, e_loc, C, H] -> [e_loc, ep*C, H]
            expert_local = recv.transpose(1, 0, 2, 3).reshape(
                e_loc, ep * capacity, H)
        else:
            expert_local = expert_in  # [E, C, H]

        def ffn(w1_e, w2_e, xin):
            h = lax.dot_general(
                xin, w1_e.astype(xin.dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(xin.dtype)
            h = nn.gelu(h, approximate=True)
            return lax.dot_general(
                h, w2_e.astype(h.dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(xin.dtype)

        expert_out = jax.vmap(ffn)(w1, w2, expert_local)
        if tp > 1:
            # row-parallel reduction: each tp rank computed a partial sum
            # over its ffn columns (same as RowParallelLinear)
            expert_out = lax.psum(expert_out, cfg.tensor_parallel_axis)

        if ep > 1:
            back = expert_out.reshape(e_loc, ep, capacity, H).transpose(
                1, 0, 2, 3)
            recv = lax.all_to_all(back, cfg.expert_parallel_axis,
                                  split_axis=0, concat_axis=0, tiled=False)
            expert_out = recv.reshape(E, capacity, H)

        # [T, E, C] x [E, C, H] -> [T, H]
        out = jnp.einsum("tec,ech->th", combine.astype(x.dtype), expert_out)
        return out.astype(x.dtype)


# ----------------------------------------------- dropless held-experts MLP
#
# Serving's expert layer (serving/mimo.py): sigmoid routing with a
# selection-only bias over ALL experts, and the MLP of the experts this
# chip HOLDS, with no capacity and no [T, E, C] one-hot: the T x k
# assignments are sorted by expert, the held ones lead, one grouped
# matmul runs over them and each token gathers its own rows back.
# ``switch_routing`` above stays the trainer's.

def route_sigmoid_topk(x, router_w, router_bias, k, norm_topk=True,
                       scaling=1.0):
    """Sigmoid top-k routing with a selection-only correction bias
    (the ``noaux_tc`` method, one group).

    x: [T, H]; router_w: [E, H]; router_bias: [E], or None for a plain
    top-k of the scores (a model trained without the bias). Scores are
    ``sigmoid(x router_w^T)`` in float32; the top ``k`` of ``score +
    bias`` are chosen; the weights are the chosen SCORES (the bias
    selects and never weighs), divided by their sum when ``norm_topk``,
    times ``scaling``. Returns ``(experts [T, k] int32, weights [T, k]
    float32)``."""
    logits = lax.dot_general(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, experts = lax.top_k(scores if router_bias is None else
                           scores + router_bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), w * scaling


def gated_mlp(x, w_gate, w_up, w_down):
    """A plain SwiGLU ``(silu(x w_gate) * x w_up) w_down`` on ``x [T,
    H]``: float32 accumulation, the gate's product in float32, results
    in x's dtype. A dense layer's MLP, and the SHARED expert a serving
    family adds to :func:`held_experts_mlp`'s partial sum: every chip of
    an expert-parallel deployment computes it alike, so across the
    shares it counts once."""
    def mm(a, w):
        return lax.dot_general(a, w, (((a.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32
                               ).astype(a.dtype)

    gate = mm(x, w_gate).astype(jnp.float32)
    up = mm(x, w_up).astype(jnp.float32)
    return mm((jax.nn.silu(gate) * up).astype(x.dtype), w_down)


GMM_TILES = (128, 1024, 1024)   # rows, contraction, columns of a step


def _effective_gmm_impl(impl, m):
    """A per-call ``impl`` is a demand; otherwise the Pallas grouped
    matmul where the default backend is a TPU and the tiles divide the
    rows, ``lax.ragged_dot`` everywhere else."""
    if impl is not None:
        if impl not in ("pallas", "ragged_dot"):
            raise ValueError(f"unknown grouped-matmul impl {impl!r}")
        return impl
    if jax.default_backend() == "tpu" and m % GMM_TILES[0] == 0:
        return "pallas"
    return "ragged_dot"


def grouped_matmul(lhs, rhs, group_sizes, *, impl=None, interpret=None):
    """``lhs[rows of group g] @ rhs[g]`` for row groups laid end to end
    from row 0: lhs [m, k], rhs [g, k, n], group_sizes [g] int32 (their
    sum may be less than m). Rows past the last group are UNDEFINED
    (the Pallas kernel visits no tile there): mask them with a select,
    never a multiply. float32 accumulation, result in lhs's dtype.

    The kernel is JAX's ``megablox.gmm`` (grid: column tile x visited
    (group, row tile) x contraction tile; a group's weights are read
    once per row tile it touches, an untouched group's never)."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    if _effective_gmm_impl(impl, m) == "ragged_dot":
        return lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)
    import importlib

    # the package's ``gmm`` attribute is its custom_vjp wrapper; the
    # kernel with the tiling and interpret arguments is the module's
    gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    tm, tk, tn = GMM_TILES
    if m % tm:
        raise ValueError(f"grouped_matmul: {m} rows do not divide by the "
                         f"row tile {tm}")
    return gmm.gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                   preferred_element_type=jnp.float32,
                   tiling=(tm, min(tk, k), min(tn, n)),
                   interpret=interpret).astype(lhs.dtype)


# the row bound engages where it spares this many 128-row tiles of the
# T * k: each engaged layer adds a second branch to its program, ~0.2 s
# of a warm set-up a layer a trunk on the v5e (PERF.md section 6), which
# only a prefill trunk of ~2,048 rows or more (at top 8, 1/16 held) pays
# back; under it the layer gains 0 - 1 ms a dispatch
HELD_ROWS_SPARED_MIN = 96


def held_row_bound(T, k, count, num_experts):
    """How many of the ``T * k`` assignment rows :func:`held_experts_mlp`
    works on when the held experts are ``count`` of ``num_experts``:
    twice the ``T * k * count / num_experts`` a uniform router sends
    here, in whole row tiles of the grouped matmul. ``T * k`` itself
    (every row: no bound) where the held experts are all there are
    (``num_experts`` None or ``count``) and where the bound would spare
    fewer than ``HELD_ROWS_SPARED_MIN`` row tiles (a decode round's few
    lanes, a short prefill trunk). The engine calls it with a prefill
    dispatch's trunk rows for the ``prefill.fetch`` span's
    ``expert_rows``."""
    total, tile = T * k, GMM_TILES[0]
    if num_experts is None or count >= num_experts:
        return total
    rows = -(-2 * total * count // (num_experts * tile)) * tile
    return rows if total - rows >= HELD_ROWS_SPARED_MIN * tile else total


def _held_assignments(experts, first, count, valid):
    """``(local [T*k], is_held [T*k])``: each assignment's index among
    the held experts, and whether it has one (and its row is a token)."""
    local = experts.reshape(-1) - first                     # [T*k]
    is_held = (local >= 0) & (local < count)
    if valid is not None:
        is_held = is_held & jnp.repeat(valid, experts.shape[1])
    return local, is_held


def _tokens_per_held(local, is_held, count):
    """How many assignments each of the ``count`` held experts has."""
    return jnp.sum(
        jax.nn.one_hot(jnp.where(is_held, local, count), count + 1,
                       dtype=jnp.int32), axis=0)[:count]


def _grouped_mlp(rows, w_gate, w_up, w_down, group_sizes, impl, interpret):
    """The experts' SwiGLU over rows grouped by expert from row 0 (scope
    ``gmm``): three grouped matmuls, the gate's product in float32. Rows
    past the last group are undefined, as :func:`grouped_matmul`'s."""
    with jax.named_scope("gmm"):
        gate = grouped_matmul(rows, w_gate, group_sizes, impl=impl,
                              interpret=interpret)
        up = grouped_matmul(rows, w_up, group_sizes, impl=impl,
                            interpret=interpret)
        inner = (jax.nn.silu(gate.astype(jnp.float32))
                 * up.astype(jnp.float32)).astype(rows.dtype)
        return grouped_matmul(inner, w_down, group_sizes, impl=impl,
                              interpret=interpret)


def _every_row(x, weights, local, is_held, w_gate, w_up, w_down, impl,
               interpret):
    """:func:`held_experts_mlp` over all ``T * k`` assignment rows,
    whoever holds their expert: ``(y, tokens_per_held)`` for any
    routing."""
    T, H = x.shape
    k = weights.shape[1]
    count = w_gate.shape[0]
    order = jnp.argsort(jnp.where(is_held, local, count), stable=True)
    tokens_per_held = _tokens_per_held(local, is_held, count)
    rows = jnp.take(x, order // k, axis=0)                  # [T*k, H]
    out = _grouped_mlp(rows, w_gate, w_up, w_down, tokens_per_held, impl,
                       interpret)
    # each assignment's row back beside its token (a gather through the
    # inverse permutation), rows of absent experts selected away
    inverse = jnp.argsort(order)
    back = jnp.take(out, inverse, axis=0).reshape(T, k, H)
    w = jnp.where(is_held.reshape(T, k), weights, 0.0)
    y = jnp.sum(jnp.where(is_held.reshape(T, k, 1),
                          back.astype(jnp.float32), 0.0)
                * w[..., None], axis=1)
    return y.astype(x.dtype), tokens_per_held


def _held_rows(x, weights, local, is_held, tokens_per_held, w_gate, w_up,
               w_down, rows, impl, interpret):
    """``y`` of :func:`held_experts_mlp` from the first ``rows`` sorted
    assignments alone, which hold every held one (the caller has seen
    ``sum(tokens_per_held) <= rows``): their rows gathered, the grouped
    matmuls over them, each output row times its own routing weight and
    summed into its token, both in float32 (a scatter-add over rows
    sorted by token: the inverse permutation and the ``[T*k, H]`` gather
    back of :func:`_every_row` go)."""
    T = x.shape[0]
    k = weights.shape[1]
    count = w_gate.shape[0]
    order = jnp.argsort(jnp.where(is_held, local, count),
                        stable=True)[:rows]
    token = order // k
    out = _grouped_mlp(jnp.take(x, token, axis=0), w_gate, w_up, w_down,
                       tokens_per_held, impl, interpret)      # [rows, H]
    # rows at or past the last held one are undefined (and their order
    # entries some unheld assignment's): selected away, sent to a token
    # past the end, where they sort last and the sum drops them. The
    # weighted float32 rows are brought into token order BEFORE the
    # scatter-add, as an array of their own: on the v5e XLA sorts an
    # unsorted scatter's rows itself at 2.5 x the time, and a scatter
    # whose updates it computes in place (the product fused into it)
    # runs at 3 x (PERF.md section 6)
    live = jnp.arange(rows) < jnp.sum(tokens_per_held)
    weighted = jnp.where(
        live[:, None],
        out.astype(jnp.float32)
        * jnp.take(weights.reshape(-1), order)[:, None], 0.0)
    token = jnp.where(live, token, T)
    by_token = jnp.argsort(token)
    y = jax.ops.segment_sum(
        jnp.take(weighted, by_token, axis=0), jnp.take(token, by_token),
        num_segments=T, indices_are_sorted=True)
    return y.astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("first", "rows", "impl",
                                             "interpret"))
def _held_rows_or_every_row(x, experts, weights, valid, w_gate, w_up,
                            w_down, *, first, rows, impl, interpret):
    """:func:`held_experts_mlp` where its row bound engages: ONE
    ``lax.cond`` on the held assignments' count, which carries rows,
    routing and the expert matrices in and ``y`` out. A ``jit`` of its
    own so that a program traces it once for all its layers of one
    shape."""
    count = w_gate.shape[0]
    local, is_held = _held_assignments(experts, first, count, valid)
    tokens_per_held = _tokens_per_held(local, is_held, count)
    y = lax.cond(
        jnp.sum(tokens_per_held) <= rows,
        lambda: _held_rows(x, weights, local, is_held, tokens_per_held,
                           w_gate, w_up, w_down, rows, impl, interpret),
        lambda: _every_row(x, weights, local, is_held, w_gate, w_up, w_down,
                           impl, interpret)[0])
    return y, tokens_per_held


def held_experts_mlp(x, experts, weights, w_gate, w_up, w_down, first,
                     valid=None, impl=None, interpret=None,
                     num_experts=None):
    """The partial MoE sum of the experts this chip holds; dropless.

    x: [T, H]; experts/weights: [T, k] from :func:`route_sigmoid_topk`
    (over ALL experts); w_gate, w_up: [count, H, F], w_down: [count, F,
    H], the held experts ``first .. first + count - 1``; valid: [T]
    bool or None: rows that are tokens (a packed batch's padding and an
    empty decode lane are not: they get 0 and cost no expert a row);
    num_experts: the router's width (None: the held experts are all).
    Returns ``(y [T, H], tokens_per_held [count] int32)``: ``y[t]`` is the weighted
    sum over t's chosen experts that are held (zero when none is) and
    the count is how many assignments each held expert received.

    The T x k assignments are sorted with the held experts' first and
    in expert order, so the grouped matmul's groups start at row 0 and
    no tile is visited past the last held row. Every assignment keeps
    its row whoever holds its expert: no capacity, nothing dropped.

    The work around the grouped matmul follows the rows that are held.
    Where :func:`held_row_bound` gives fewer rows than ``T * k`` (a
    long prefill trunk under a cut layer: twice a uniform router's share),
    ONE ``lax.cond`` on the held count, which is on the device before
    anything is gathered, picks between two forms of the same sum: at or
    under the bound only the first ``rows`` sorted assignments are
    gathered, multiplied and summed into their tokens; over it (a router
    that sends this chip more than twice its share) all ``T * k`` are, as
    everywhere the bound does not engage. Either way the layer is
    dropless and the float32 sum holds the same terms; only their order
    within a token may differ."""
    T = x.shape[0]
    k = experts.shape[1]
    count = w_gate.shape[0]
    rows = held_row_bound(T, k, count, num_experts)
    if rows < T * k:
        return _held_rows_or_every_row(
            x, experts, weights, valid, w_gate, w_up, w_down, first=first,
            rows=rows, impl=impl, interpret=interpret)
    local, is_held = _held_assignments(experts, first, count, valid)
    return _every_row(x, weights, local, is_held, w_gate, w_up, w_down,
                      impl, interpret)
