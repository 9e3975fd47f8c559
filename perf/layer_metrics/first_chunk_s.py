"""``chunk.dispatch`` + ``chunk.fetch`` of the first chunk of the
measured ``pretrain.main`` call: trace + lower + compile or cache load
of the step, and its first steps. The part of ``setup_s`` that a trainer
which kept its ``jit`` between calls would not pay. Program spans."""

from perf.span_ring import train_chunks


def read(record):
    chunks = train_chunks(record)
    if chunks is None:
        return None
    _, kids = chunks[0]
    return kids["chunk.dispatch"] + kids["chunk.fetch"]
