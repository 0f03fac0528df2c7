"""Device milliseconds of the relabel phase (core/relabel), per graph."""

MODULES = ('jit_relabel_ring', 'jit_relabel_recompute')


def read(reading):
    seconds = reading.trace.module_s_per_unit(MODULES)
    return None if seconds is None else 1e3 * seconds
