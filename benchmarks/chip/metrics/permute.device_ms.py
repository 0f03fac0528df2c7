"""Device milliseconds of the gathers and scatters by a sort order, in
every phase, per graph: the own time of the device operations whose kind
scope is `permute` (scopes.py)."""

import scopes


def read(reading):
    return scopes.ms_per_unit(reading, lambda path: scopes.kind_of(path) == "permute")
