"""Device milliseconds of the collectives, per graph, averaged over the
chips: the own time of the device operations whose kind scope is
`collective` (scopes.py): the shuffle rounds' all_to_all, the ring
relabel's ppermute, and the redistribute's all_to_all, psum and pmax."""

import scopes


def read(reading):
    return scopes.ms_per_unit(reading, lambda path: scopes.kind_of(path) == "collective")
