"""The recurrence in a prefill dispatch against the least time the chip
could take: the larger of (every token's vectors in and out and each
sequence's final state written, once a layer / peak bytes a second) and
(``4 H P N`` operations a live token a layer / peak operations a
second), ``perf/falcon_h1_costs.py``: the recurrence's own work, not a
chunked form's, so a change of chunk or algorithm does not move the
yardstick. Tokens and sequences are ``prefill.fetch``'s ``ssm_tokens``
and ``ssm_segments`` of the dispatches the trace holds (the window's
first ``runs`` of them), over the device time of the ops under
``layer/ssm/scan`` in one run of the prefill program. Nothing where the
program records no such counter or scope. Device trace."""

from perf import falcon_h1_costs
from perf.layer_metrics.sparse_prefill_roofline import (
    seconds_under, traced_spans)
from perf.layer_metrics.ssm_decode_roofline import SCOPES


def read(record):
    prefill = (record.get("scopes") or {}).get("jit__prefill")
    model, peak = record.get("model") or {}, record.get("peak")
    if not prefill or not peak or "ssm_state" not in model:
        return None
    fetched = traced_spans(record, "prefill.fetch", "ssm_tokens",
                           prefill["runs"])
    seconds = seconds_under(prefill, SCOPES)
    if not fetched or not seconds:
        return None

    def mean(cost):
        return sum(cost(f) for f in fetched) / len(fetched)

    floor = max(mean(lambda f: falcon_h1_costs.prefill_bytes(
        model, f["ssm_tokens"], f["ssm_segments"]))
        / peak["hbm_bytes_per_s"],
        mean(lambda f: falcon_h1_costs.recurrence_flops(
            model, f["ssm_tokens"])) / peak["bf16_flops_per_s"])
    return 100.0 * floor / seconds
