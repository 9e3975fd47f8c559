"""The full layers' selection and attention in a decode round against the
least time the chip could take: the larger of ([``index_rows_scored`` x
128 + ``sparse_rows_selected`` x 576] x 2 bytes a full layer / peak bytes
a second) and (the same rows' operations / peak operations a second),
``perf/dots3_costs.py``, from ``engine.round``'s counters of the rounds
the trace holds (the window's first ``runs`` that decoded), over the
device time of the ops under ``layer/attn_sparse/{index,select,attend}``
in one run of the decode program. Nothing where the program records no
such counter or scope. Device trace."""

from perf import dots3_costs
from perf.layer_metrics.sparse_prefill_roofline import (
    SCOPES, seconds_under, traced_spans)


def read(record):
    decode = (record.get("scopes") or {}).get("jit__decode")
    model, peak = record.get("model") or {}, record.get("peak")
    if not decode or not peak or "index_topk" not in model:
        return None
    rounds = traced_spans(record, "engine.round", "index_rows_scored",
                          decode["runs"])
    seconds = seconds_under(decode, SCOPES)
    if not rounds or not seconds:
        return None

    def mean(cost):
        return sum(cost(model, r["index_rows_scored"],
                        r["sparse_rows_selected"]) for r in rounds) \
            / len(rounds)

    floor = max(mean(dots3_costs.sparse_decode_bytes)
                / peak["hbm_bytes_per_s"],
                mean(dots3_costs.sparse_decode_flops)
                / peak["bf16_flops_per_s"])
    return 100.0 * floor / seconds
