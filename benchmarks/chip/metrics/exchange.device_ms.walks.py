"""Device milliseconds of the walkers' exchange (capacity_all_to_all on
every hop, bucketed by bucket_by_destination), per walk call: the own time
of the device operations under the `exchange` scope (scopes.py).  The walk
cells' share of `exchange.device_ms`, which moves `walk_hops_per_s`."""

import scopes


def read(reading):
    return scopes.ms_per_unit(reading, lambda path: scopes.EXCHANGE in path)
