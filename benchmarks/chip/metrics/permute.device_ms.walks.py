"""Device milliseconds of the gathers and scatters by a sort order in a
walk call (the exchange's `dest[order]` and rank scatter on every hop), per
call: the own time of the device operations whose kind scope is `permute`
(scopes.py).  The walk cells' share of `permute.device_ms`, which moves
`walk_hops_per_s`."""

import scopes


def read(reading):
    return scopes.ms_per_unit(reading, lambda path: scopes.kind_of(path) == "permute")
