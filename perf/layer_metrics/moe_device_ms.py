"""Device milliseconds of one run of the decode program under the scope
``layer/moe`` (route + experts, every expert layer): the ``XLA Ops`` of
the traced window joined with the decode program's HLO by
``perf/scope_account.py``. Device trace."""


def read(record):
    decode = (record.get("scopes") or {}).get("jit__decode")
    if not decode:
        return None
    seconds = sum(s for scope, s in decode["seconds"].items()
                  if scope.startswith("layer/moe"))
    return 1e3 * seconds / decode["runs"] if seconds else None
