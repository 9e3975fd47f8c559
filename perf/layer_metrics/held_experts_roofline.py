"""The grouped expert matmuls of a decode round against the bytes they
must move: ``moe_experts_roofline``'s quantity, by the same count
(``perf/mimo_costs.py`` ``experts_bytes``: held experts that received a
token x their three matrices + the activations of the assignments they
served, mean over the window's decode-only rounds) / the chip's peak
bytes a second / the device time of the ops under
``layer/moe/experts/gmm`` in one run of the decode program, for a family
whose rounds are picked by the expert counters alone
(``held_experts_touched``). The shared expert is not in it (its scope is
``layer/moe/shared``). Nothing where the program records no such counter
or scope. Device trace."""

from perf import mimo_costs
from perf.layer_metrics.held_experts_touched import decode_round_counts


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
