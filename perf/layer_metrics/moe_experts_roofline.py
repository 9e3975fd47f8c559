"""The grouped expert matmuls of a decode round against the bytes they
must move: (held experts that received a token x their three matrices +
the activations of the assignments they served, ``perf/mimo_costs.py``,
from the program's counters: mean over the window's decode-only rounds)
/ the chip's peak bytes a second / the device time of the ops under
``layer/moe/experts/gmm`` in one run of the decode program. The same
count whatever implements the layer. Device trace."""

from perf import mimo_costs
from perf.layer_metrics.experts_touched_mean import decode_round_counts


def read(record):
    decode = (record.get("scopes") or {}).get("jit__decode")
    counts = decode_round_counts(record)
    if not decode or not counts or not record.get("peak"):
        return None
    seconds = decode["seconds"].get("layer/moe/experts/gmm", 0.0) \
        / decode["runs"]
    if not seconds:
        return None
    touched = sum(c["experts_touched"] for c in counts) / len(counts)
    assigned = sum(c["expert_tokens_sum"] for c in counts) / len(counts)
    floor = mimo_costs.experts_bytes(record["model"], touched, assigned) \
        / record["peak"]["hbm_bytes_per_s"]
    return 100.0 * floor / seconds
