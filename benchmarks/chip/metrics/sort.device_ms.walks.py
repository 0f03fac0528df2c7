"""Device milliseconds of the sorts of a walk call (the exchange's bucket
rank on every hop), per call: the own time of the device operations whose
kind scope is `sort` (scopes.py).  The walk cells' share of
`sort.device_ms`, which moves `walk_hops_per_s`."""

import scopes


def read(reading):
    return scopes.ms_per_unit(reading, lambda path: scopes.kind_of(path) == "sort")
