"""Process start to window open: import, weights from the seed, compile
or cache load, warm-up, calibration and ramp."""


def read(record):
    return record["setup_s"]
