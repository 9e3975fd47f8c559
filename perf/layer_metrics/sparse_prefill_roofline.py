"""The full layers' selection and attention in a prefill against the
least arithmetic an exact form does: [the indexer over every causal pair
(``index_pairs`` x 64 heads x 128 x 2) + the expanded attention over the
selected pairs alone (``sparse_pairs`` x 128 heads x (192 + 128) x 2)] a
full layer (``perf/dots3_costs.py``) / the chip's peak operations a
second, over the device time of the ops under
``layer/attn_sparse/{index,select,attend}`` in one run of the prefill
program. The pairs are ``prefill.fetch``'s of the dispatches the trace
holds (the window's first ``runs`` of them: the trace opens with the
window), so time and work are of the same runs. A masked dense form does
the arithmetic of EVERY causal pair, so it reads under the selected
share. Nothing where the program records no such counter or scope.
Device trace."""

from perf import dots3_costs
from perf.span_ring import serve_window

SCOPES = tuple(f"layer/attn_sparse/{part}"
               for part in ("index", "select", "attend"))


def traced_spans(record, name, key, runs):
    """The attributes of the window's first ``runs`` spans ``name`` that
    carry ``key``: those of the traced stretch, which opens the window."""
    cut = serve_window(record)
    if cut is None or not runs:
        return None
    t_open, _, records = cut
    found = sorted((r for r in records if r.name == name and r.t0 >= t_open
                    and r.attrs and key in r.attrs), key=lambda r: r.t0)
    return [r.attrs for r in found[:runs]] if len(found) >= runs else None


def seconds_under(program, scopes):
    return sum(program["seconds"].get(s, 0.0) for s in scopes) \
        / program["runs"]


def read(record):
    prefill = (record.get("scopes") or {}).get("jit__prefill")
    model, peak = record.get("model") or {}, record.get("peak")
    if not prefill or not peak or "index_topk" not in model:
        return None
    fetched = traced_spans(record, "prefill.fetch", "index_pairs",
                           prefill["runs"])
    seconds = seconds_under(prefill, SCOPES)
    if not fetched or not seconds:
        return None
    flops = sum(dots3_costs.sparse_prefill_flops(
        model, f["index_pairs"], f["sparse_pairs"]) for f in fetched) \
        / len(fetched)
    return 100.0 * flops / peak["bf16_flops_per_s"] / seconds
