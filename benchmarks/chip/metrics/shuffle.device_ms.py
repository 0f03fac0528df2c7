"""Device milliseconds of the shuffle phase (core/shuffle), per graph."""

MODULES = ('jit_distributed_shuffle', 'jit_shuffle_recompute')


def read(reading):
    seconds = reading.trace.module_s_per_unit(MODULES)
    return None if seconds is None else 1e3 * seconds
