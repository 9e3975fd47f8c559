"""Share of the window the engine spent outside dispatch + fetch
(``engine.device_dispatch_s``): scheduling, staging, bookkeeping."""


def read(record):
    return 100.0 * (1.0 - record["device_dispatch_s"] / record["window_s"])
