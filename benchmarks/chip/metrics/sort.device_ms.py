"""Device milliseconds of the sorts of every phase (each argsort), per
graph: the own time of the device operations whose kind scope is `sort`
(scopes.py).  A scatter that the compiler carries out by sorting its
indices keeps the kind of the scatter."""

import scopes


def read(reading):
    return scopes.ms_per_unit(reading, lambda path: scopes.kind_of(path) == "sort")
