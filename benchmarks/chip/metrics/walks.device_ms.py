"""Device milliseconds of the walk program (data/walks.distributed_walks,
with capacity_all_to_all on every hop), per call."""

MODULES = ('jit_distributed_walks',)


def read(reading):
    seconds = reading.trace.module_s_per_unit(MODULES)
    return None if seconds is None else 1e3 * seconds
