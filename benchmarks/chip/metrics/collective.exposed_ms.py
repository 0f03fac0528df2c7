"""Device milliseconds of the collectives, per graph, during which no other
operation runs on the same chip, averaged over the chips: of
`collective.device_ms`, the part that no compute hides (overlap.py)."""

import overlap
import scopes


def read(reading):
    seconds = overlap.exposed_s_per_unit(reading, lambda path: scopes.kind_of(path) == "collective")
    return None if seconds is None else 1e3 * seconds
