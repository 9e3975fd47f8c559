"""The share of the prefill program's device time spent under the scopes
``layer/attn_sparse`` (the full layers' latent attention with its
indexer, selection, projections, gate and writes) and
``layer/attn_window_latent`` (the sliding layers' latent attention): the
part of a prefill no other cell runs, over the whole program's time, mean
over the traced window's runs of the prefill program. Nothing where the
program has no such scopes. Device trace."""


def read(record):
    prefill = (record.get("scopes") or {}).get("jit__prefill")
    if not prefill or not prefill.get("total_s"):
        return None
    seconds = sum(s for scope, s in prefill["seconds"].items()
                  if scope.startswith(("layer/attn_sparse",
                                       "layer/attn_window_latent")))
    return 100.0 * seconds / prefill["total_s"] if seconds else None
