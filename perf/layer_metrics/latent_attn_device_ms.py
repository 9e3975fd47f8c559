"""Device milliseconds of one run of the decode program under the scope
``layer/attn_latent`` (projections, rotary, the row's write, the
absorbed products, the attention kernel, ``wo``; every layer): the ``XLA
Ops`` of the traced window joined with the decode program's HLO by
``perf/scope_account.py``. Nothing where the program has no such scope.
Device trace."""


def read(record):
    decode = (record.get("scopes") or {}).get("jit__decode")
    if not decode:
        return None
    seconds = sum(s for scope, s in decode["seconds"].items()
                  if scope.startswith("layer/attn_latent"))
    return 1e3 * seconds / decode["runs"] if seconds else None
