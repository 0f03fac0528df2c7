"""Device milliseconds of the merge of received runs, per graph, averaged
over the chips: the own time of the device operations whose kind scope is
`merge` (scopes.py), the redistribute's nb-way merge of the sorted runs
that arrived (redistribute.merge_received)."""

import scopes


def read(reading):
    return scopes.ms_per_unit(reading, lambda path: scopes.kind_of(path) == "merge")
