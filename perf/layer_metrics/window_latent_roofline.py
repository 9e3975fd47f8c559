"""The sliding layers' decode attention against the bytes it must move:
the ring rows inside each slot's window (``engine.round``'s
``window_rows``, of the rounds the trace holds) x 1,088 live columns x 2
bytes a sliding layer (``perf/dots3_costs.py``) / the chip's peak bytes a
second, over the device time of the ops under
``layer/attn_window_latent/attend`` in one run of the decode program.
Nothing where the program records no such counter or scope. Device
trace."""

from perf import dots3_costs
from perf.layer_metrics.sparse_prefill_roofline import (
    seconds_under, traced_spans)


def read(record):
    decode = (record.get("scopes") or {}).get("jit__decode")
    model, peak = record.get("model") or {}, record.get("peak")
    if not decode or not peak or "window_width" not in model:
        return None
    rounds = traced_spans(record, "engine.round", "window_rows",
                          decode["runs"])
    seconds = seconds_under(decode, ("layer/attn_window_latent/attend",))
    if not rounds or not seconds:
        return None
    rows = sum(r["window_rows"] for r in rounds) / len(rounds)
    return 100.0 * dots3_costs.window_bytes(model, rows) \
        / peak["hbm_bytes_per_s"] / seconds
