"""The share of the prefill program's device time spent under the scopes
``layer/attn_latent`` (latent attention in its expanded form, with its
projections and the rows' write) and ``layer/moe/shared`` (the shared
expert): the two parts a latent-attention model with a shared expert
adds to a prefill, over the whole program's time, mean over the traced
window's runs of the prefill program. Nothing where the program has no
such scopes. Device trace."""


def read(record):
    prefill = (record.get("scopes") or {}).get("jit__prefill")
    if not prefill or not prefill.get("total_s"):
        return None
    seconds = sum(s for scope, s in prefill["seconds"].items()
                  if scope.startswith(("layer/attn_latent",
                                       "layer/moe/shared")))
    return 100.0 * seconds / prefill["total_s"] if seconds else None
