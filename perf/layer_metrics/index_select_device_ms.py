"""Device milliseconds of one run of the prefill program under the scopes
``layer/attn_sparse/index`` (the index keys and queries, the [query, key]
scores in blocks) and ``layer/attn_sparse/select`` (the k-th largest a
query, the mask), every full layer: what the selection costs before any
attention is computed. Nothing where the program has no such scopes.
Device trace."""


def read(record):
    prefill = (record.get("scopes") or {}).get("jit__prefill")
    if not prefill:
        return None
    seconds = sum(prefill["seconds"].get(f"layer/attn_sparse/{part}", 0.0)
                  for part in ("index", "select"))
    return 1e3 * seconds / prefill["runs"] if seconds else None
