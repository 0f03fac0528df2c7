"""Device milliseconds of the exchange (capacity_all_to_all and
return_all_to_all, under whichever phase calls them), per graph: the own
time of the device operations under the `exchange` scope (scopes.py)."""

import scopes


def read(reading):
    return scopes.ms_per_unit(reading, lambda path: scopes.EXCHANGE in path)
