"""Tokens trained a second by the whole cell (all its chips): every token
of every steady chunk over the whole window, harness clock."""


def read(record):
    steps = record["chunk_steps"] * len(record["chunks"])
    return steps * record["tokens_per_step"] / record["window_s"]
