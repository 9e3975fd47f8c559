"""A serial round that ran a prefill returns at its first tokens and
the next ``step`` call runs its decode half (``ServingEngine.step``).
What the tests that walk an engine round by round share."""


def round_open(engine, info):
    """Did the call that returned ``info`` leave its round open (a
    prefill half)? ``engine.tick`` advances when a round closes."""
    return info["tick"] == engine.tick


def whole_round(engine, arrivals=None):
    """One scheduler round through both its calls: the opening call's
    info with the decode half's lanes and verifies in it."""
    info = engine.step(arrivals=arrivals)
    if round_open(engine, info):
        half = engine.step()
        assert not (half["evicted"] or half["admitted"]
                    or half["prefilled"] or half["shed"]), half
        assert half["tick"] == info["tick"] and not round_open(engine, half)
        info = dict(info, verified=half["verified"],
                    decoded_slots=half["decoded_slots"])
    return info
