"""Output tokens that reached their callers inside the window, over the
window's seconds (all requests, whenever they were sent)."""


def read(record):
    return sum(r["new_tokens"] for r in record["rounds"]) / record["window_s"]
