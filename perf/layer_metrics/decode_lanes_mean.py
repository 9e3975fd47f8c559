"""Mean share of the decode lanes that carried a request, over the
window's rounds."""


def read(record):
    rounds = record["rounds"]
    return 100.0 * sum(r["decoded_slots"] for r in rounds) \
        / (len(rounds) * record["num_slots"])
