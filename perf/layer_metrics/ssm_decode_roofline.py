"""The recurrence in a decode round against the least time the chip
could take: the larger of (every live slot's state read and written at
its dtype, and its token's vectors, once a layer / peak bytes a second)
and (``4 H P N`` operations a live slot a layer / peak operations a
second), ``perf/falcon_h1_costs.py``, from ``engine.round``'s
``ssm_slots_live`` of the rounds the trace holds (the window's first
``runs`` that decoded), over the device time of the ops under
``layer/ssm/scan`` in one run of the decode program. Nothing where the
program records no such counter or scope. Device trace."""

from perf import falcon_h1_costs
from perf.layer_metrics.sparse_prefill_roofline import seconds_under
from perf.span_ring import serve_window

SCOPES = ("layer/ssm/scan",)


def traced_rounds(record, runs):
    """``ssm_slots_live`` of the window's first ``runs`` rounds that
    decoded: those of the traced stretch, which opens the window."""
    cut = serve_window(record)
    if cut is None or not runs:
        return None
    t_open, _, records = cut
    found = sorted((r for r in records if r.name == "engine.round"
                    and r.t0 >= t_open and r.attrs.get("decoded")
                    and "ssm_slots_live" in r.attrs), key=lambda r: r.t0)
    return [r.attrs["ssm_slots_live"] for r in found[:runs]] \
        if len(found) >= runs else None


def read(record):
    decode = (record.get("scopes") or {}).get("jit__decode")
    model, peak = record.get("model") or {}, record.get("peak")
    if not decode or not peak or "ssm_state" not in model:
        return None
    slots = traced_rounds(record, decode["runs"])
    seconds = seconds_under(decode, SCOPES)
    if not slots or not seconds:
        return None
    slots = sum(slots) / len(slots)
    floor = max(falcon_h1_costs.decode_bytes(model, slots)
                / peak["hbm_bytes_per_s"],
                falcon_h1_costs.recurrence_flops(model, slots)
                / peak["bf16_flops_per_s"])
    return 100.0 * floor / seconds
