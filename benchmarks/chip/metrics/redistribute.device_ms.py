"""Device milliseconds of the redistribute phase (core/redistribute, with
capacity_all_to_all), per graph."""

MODULES = ('jit_redistribute_sorted',)


def read(reading):
    seconds = reading.trace.module_s_per_unit(MODULES)
    return None if seconds is None else 1e3 * seconds
