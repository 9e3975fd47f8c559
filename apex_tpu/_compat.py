"""Readers for XLA's cost and memory analyses.

The cost accounting layer (``apex_tpu.telemetry.costs``) wants one
plain-dict shape from an AOT stage. On the jax this tree runs (0.9.0)
``Lowered.cost_analysis()`` and ``Compiled.cost_analysis()`` both return
a flat ``{metric: float}`` dict and ``Compiled.memory_analysis()``
returns a ``CompiledMemoryStats`` object (attributes, not keys); a
backend that does not report returns None. "Can't report" is a value
here (None); anything else the call raises is the caller's to see.
"""

# CompiledMemoryStats attribute names → the one key set the cost block
# speaks. Every field is device-side; the host_* twins are ignored.
_MEMORY_FIELDS = (
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "temp_size_in_bytes",
    "alias_size_in_bytes",
    "generated_code_size_in_bytes",
)


def cost_analysis_dict(stage):
    """The flat ``{metric: float}`` dict of a ``Lowered`` or
    ``Compiled`` stage's ``cost_analysis()``, or None when the backend
    reports nothing."""
    raw = stage.cost_analysis()
    return dict(raw) if raw else None


def memory_analysis_dict(compiled):
    """One plain dict (``argument/output/temp/alias/generated_code
    _size_in_bytes`` ints) from ``Compiled.memory_analysis()``, or None
    when the backend reports nothing (None, or a stats object with
    every field 0 — a stubbed surface carries no information)."""
    raw = compiled.memory_analysis()
    if raw is None:
        return None
    out = {field: int(getattr(raw, field)) for field in _MEMORY_FIELDS}
    return out if any(out.values()) else None
