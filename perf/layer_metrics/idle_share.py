"""1 - union of device-op intervals / traced window, from the xplane; the
mean over the chips used."""


def read(record):
    trace = record.get("trace")
    return None if not trace else 100.0 * trace["idle_share"]
