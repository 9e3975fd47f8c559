"""The decode-attention ops of both layer kinds against the bytes they
must move: (pages of the pool that hold context + ring pages that hold
part of a window, each K and V page unpadded, once a layer of its kind:
``perf/mimo_costs.py``, from ``engine.round``'s ``global_pages_live``
and ``window_pages``, mean over the window's decode-only rounds) / the
chip's peak bytes a second / the device time of the ops under
``layer/attn_global/attend`` and ``layer/attn_window/attend`` in one run
of the decode program. Device trace."""

from perf import mimo_costs
from perf.layer_metrics.experts_touched_mean import decode_round_counts


def read(record):
    decode = (record.get("scopes") or {}).get("jit__decode")
    counts = decode_round_counts(record)
    if not decode or not counts or not record.get("peak"):
        return None
    seconds = sum(decode["seconds"].get(f"layer/attn_{kind}/attend", 0.0)
                  for kind in ("global", "window")) / decode["runs"]
    if not seconds:
        return None
    model = record["model"]
    floor = mimo_costs.attend_bytes(
        model, model["page_size"],
        sum(c["global_pages_live"] for c in counts) / len(counts),
        sum(c["window_pages"] for c in counts) / len(counts)) \
        / record["peak"]["hbm_bytes_per_s"]
    return 100.0 * floor / seconds
