"""The share of the decode program's device time spent under the scope
``layer/ssm`` (the mixer: its projections, the conv over each slot's
last inputs, the in-place update of each slot's state, the gated norm),
over the whole program's time, mean over the traced window's runs of the
decode program. Nothing where the program has no such scope. Device
trace."""


def read(record):
    decode = (record.get("scopes") or {}).get("jit__decode")
    if not decode or not decode.get("total_s"):
        return None
    seconds = sum(s for scope, s in decode["seconds"].items()
                  if scope.startswith("layer/ssm"))
    return 100.0 * seconds / decode["total_s"] if seconds else None
