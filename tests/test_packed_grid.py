"""The packed prefill kernel's grid (``ops/attention_pallas.py``): the
table of live block pairs it is built from, the heads a step takes, the
kernel under every such count against the ``jnp`` form and against one
head a step, and the count of steps ``prefill.fetch`` carries. CPU,
interpret mode; the compiler's verdict at the families' shapes is in
``test_decode_attention_mosaic.py``."""

import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.ops import attention as attn
from apex_tpu.ops import attention_pallas as ap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ------------------------------------------------ the table of live pairs

LIVE = {   # S: pairs a head without a window, with one of 513, of 128
    256: (1, 1, 1), 512: (3, 3, 3), 1024: (10, 9, 7), 2048: (36, 21, 15),
    4096: (136, 45, 31), 8192: (528, 93, 63)}


@pytest.mark.parametrize("window", [None, 513, 128])
@pytest.mark.parametrize("S", sorted(LIVE))
def test_live_pairs_are_the_pairs_a_query_can_see_into(S, window):
    """From the distances a block pair holds (row - col runs from
    ``(iq - ik) * blk - (blk - 1)`` to ``(iq - ik) * blk + blk - 1``): a
    pair is listed exactly when some distance is causal and inside the
    window, which is the rule the kernel's grid of every pair skipped
    by; it CROSSES exactly when some distance is not; the list is q
    block major with k blocks ascending, and every q block has one
    first and one last pair, at its ends."""
    blk = ap.packed_block(S)
    n = S // blk
    iq, ik, flags = ap.packed_live_pairs(S, blk, window)
    reach = S if window is None else window
    want, hidden_in = [], {}
    for a in range(n):
        for b in range(n):
            lo, hi = (a - b) * blk - (blk - 1), (a - b) * blk + blk - 1
            if hi >= 0 and lo < reach:
                want.append((a, b))
                hidden_in[a, b] = lo < 0 or hi >= reach
    assert list(zip(iq.tolist(), ik.tolist())) == want
    assert len(want) == LIVE[S][(None, 513, 128).index(window)]
    # the rule of the grid that held every pair (its ``live``)
    old = [(a, b) for a in range(n) for b in range(n) if b <= a and (
        window is None or (b + 1) * blk - 1 > a * blk - window)]
    assert old == want
    assert [bool(f & ap.PAIR_CROSSES) for f in flags] == \
        [hidden_in[p] for p in want]
    for a in range(n):
        mine = np.flatnonzero(iq == a)
        first = [bool(flags[i] & ap.PAIR_FIRST) for i in mine]
        last = [bool(flags[i] & ap.PAIR_LAST) for i in mine]
        assert first == [True] + [False] * (len(mine) - 1)
        assert last == [False] * (len(mine) - 1) + [True]


def test_a_pack_shorter_than_a_block_is_one_pair():
    assert ap.packed_live_pairs(64, 64, None).tolist() == [[0], [0], [7]]
    assert ap.packed_live_pairs(64, 64, 8).tolist() == [[0], [0], [7]]


# ----------------------------------------------------- the heads a step

@pytest.mark.parametrize("hq,n_kv,dk,dv,selected,heads", [
    (64, 4, 192, 128, False, 8),      # MiMo, global layers: 16 a KV head
    (64, 8, 192, 128, False, 8),      # MiMo, window layers: 8 a KV head
    (64, 64, 192, 128, False, 8),     # A.X-K1: a KV head a query head
    (128, 128, 192, 128, True, 8),    # dots3, full layers under a selection
    (64, 64, 256, 128, False, 8),     # dots3, sliding layers
    (64, 16, 192, 128, False, 4),     # four queries a KV head: no more
    (6, 6, 192, 128, False, 2),       # what divides
    (7, 7, 192, 128, False, 1),
    (64, 64, 512, 512, False, 4),     # what fits
], ids=["mimo-global", "mimo-window", "axk1", "dots3-full", "dots3-sliding",
        "inside-a-kv-head", "divides-6", "divides-7", "fits"])
def test_heads_a_step_follow_the_head_counts_and_the_widths(
        hq, n_kv, dk, dv, selected, heads):
    assert ap.packed_heads_a_step(hq, n_kv, 256, dk, dv, 2,
                                  selected) == heads
    blocks = ap._packed_vmem_bytes(heads, 1 if hq > n_kv else heads, 256,
                                   dk, dv, 2, selected)
    assert blocks <= ap._PACKED_VMEM_BUDGET < 16 << 20


@pytest.mark.parametrize("S,steps,dense", [
    (8192, 3 * 16 * 528 + 6 * 8 * 93, 786432),
    (4096, 3 * 16 * 136 + 6 * 8 * 45, 196608),
    (2048, 3 * 16 * 36 + 6 * 8 * 21, 49152),
    (1024, 3 * 16 * 10 + 6 * 8 * 9, 12288),
])
def test_prefill_fetch_counts_the_grid_steps_of_a_dots3_dispatch(
        monkeypatch, S, steps, dense):
    """The cell's configuration: three full layers of 128 heads, six
    sliding ones of 64 under a window of 513; ``attend_steps_dense`` is
    the count of a grid with one step a (head, q block, k block). On
    the CPU, where the ``jnp`` form runs, both read 0."""
    from apex_tpu.serving import family

    with open(os.path.join(ROOT, "perf/configs/dots3-note-ep16.json")) as fh:
        cfg = family.config_from_dict(json.load(fh))
    extras = {"expert_tokens": np.zeros((8, 16), np.int32),
              "index_pairs": 0, "sparse_pairs": 0}
    attrs = family.family_of(cfg).prefill_attrs
    off = attrs(cfg, extras, S)
    assert (off["attend_steps"], off["attend_steps_dense"]) == (0, 0)
    monkeypatch.setattr(attn, "_tpu_available", lambda: True)
    on = attrs(cfg, extras, S)
    assert (on["attend_steps"], on["attend_steps_dense"]) == (steps, dense)
    assert {"expert_rows", "index_pairs", "sparse_pairs"} <= set(on)


@pytest.mark.parametrize("name,S,steps,dense", [
    ("axk1-ep16", 4096, 6 * 8 * 136, 6 * 64 * 256),
    ("axk1-ep16", 512, 6 * 8 * 3, 6 * 64 * 4),
    ("mimo-v2.5-ep16", 2048, 8 * (2 * 36 + 5 * 15), 7 * 64 * 64),
    ("mimo-v2.5-ep16", 256, 7 * 8, 7 * 64),
])
def test_prefill_fetch_counts_the_grid_steps_of_the_other_families(
        monkeypatch, name, S, steps, dense):
    from apex_tpu.serving import family

    with open(os.path.join(ROOT, f"perf/configs/{name}.json")) as fh:
        cfg = family.config_from_dict(json.load(fh))
    monkeypatch.setattr(attn, "_tpu_available", lambda: True)
    held = 12 if name.startswith("axk1") else 16
    got = family.family_of(cfg).prefill_attrs(
        cfg, {"expert_tokens": np.zeros((5, held), np.int32)}, S)
    assert (got["attend_steps"], got["attend_steps_dense"]) == (steps, dense)


# ------------------------------- the kernel, at every count of heads a step

CASES = {   # hq, n_kv, S, window, sink, selected
    "plain": (8, 2, 768, None, False, False),
    "window": (8, 2, 1024, 300, False, False),
    "window-of-a-block": (4, 4, 1024, 256, False, False),
    "sink": (8, 4, 512, None, True, False),
    "selected": (8, 8, 768, None, False, True),
    "grouped-kv-heads": (16, 2, 512, 128, True, False),
    "a-kv-head-a-query-head": (8, 8, 512, None, False, False),
    "segments-end-inside-a-block": (4, 1, 768, None, False, False),
}
DK, DV = 48, 32


def _heads_of(case):
    hq, n_kv = CASES[case][:2]
    inside = hq // n_kv if hq > n_kv else hq
    return [g for g in (1, 2, 4, 8) if inside % g == 0]


@functools.lru_cache(maxsize=None)
def _inputs(case):
    hq, n_kv, S, window, sink, selected = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 5)
    cuts = [S // 2 - 37, S // 3 + 5]       # neither ends on a block
    seg = np.repeat([1, 2, 0], cuts + [S - sum(cuts)])
    return dict(
        q=jax.random.normal(keys[0], (hq, S, DK)),
        k=jax.random.normal(keys[1], (n_kv, S, DK)),
        v=jax.random.normal(keys[2], (n_kv, S, DV)),
        seg=jnp.asarray(seg, jnp.int32), window=window,
        sink=jax.random.normal(keys[3], (hq,)) if sink else None,
        selected=(jax.random.uniform(keys[4], (S, S)) < 0.4).astype(jnp.int8)
        if selected else None)


def _kernel(case, heads, monkeypatch):
    a = _inputs(case)
    hq, n_kv, S = CASES[case][:3]
    monkeypatch.setattr(ap, "_HEADS_A_STEP", (heads, 1))
    # float32 blocks are twice the families' bfloat16: the arithmetic is
    # checked at every count here, what fits in the test above
    monkeypatch.setattr(ap, "_PACKED_VMEM_BUDGET", 1 << 30)
    assert ap.packed_heads_a_step(hq, n_kv, ap.packed_block(S), DK, DV, 4,
                                  a["selected"] is not None) == heads
    return ap.packed_gqa_attention_pallas(
        a["q"], a["k"], a["v"], a["seg"], 0.1, window=a["window"],
        sink=a["sink"], selected=a["selected"], interpret=True)


@functools.lru_cache(maxsize=None)
def _one_head_a_step(case):
    with pytest.MonkeyPatch.context() as mp:
        return _kernel(case, 1, mp)


@pytest.mark.parametrize("case,heads", [
    (case, g) for case in CASES for g in _heads_of(case)])
def test_kernel_equals_the_jnp_form_and_one_head_a_step_to_the_bit(
        monkeypatch, case, heads):
    a = _inputs(case)
    got = _kernel(case, heads, monkeypatch)
    plain = attn._packed_gqa_dense(a["q"], a["k"], a["v"], a["seg"], 0.1,
                                   a["window"], a["sink"], a["selected"])
    np.testing.assert_allclose(got, plain, atol=2e-6)
    assert np.array_equal(np.asarray(got), np.asarray(_one_head_a_step(case)))


@pytest.mark.parametrize("heads", [8, 4, 2, 1])
def test_kernel_takes_its_heads_a_step_from_the_rule(monkeypatch, heads):
    """The public entry has no argument for it: the grid of the call it
    traces is ``(hq / heads, live pairs)`` with ``heads`` from
    :func:`packed_heads_a_step` (the inner ``jit``'s cache is keyed on
    it), the table's three rows prefetched."""
    a = _inputs("a-kv-head-a-query-head")
    monkeypatch.setattr(ap, "_HEADS_A_STEP", (heads, 1))
    jaxpr = jax.make_jaxpr(lambda q, k, v, seg: ap.packed_gqa_attention_pallas(
        q, k, v, seg, 0.1, interpret=True))(a["q"], a["k"], a["v"], a["seg"])
    inner, = [e for e in jaxpr.jaxpr.eqns
              if e.primitive.name in ("pjit", "jit")]
    call, = [e for e in inner.params["jaxpr"].jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    grid = call.params["grid_mapping"]
    assert tuple(grid.grid) == (8 // heads, 3)
    assert grid.num_index_operands == 3
