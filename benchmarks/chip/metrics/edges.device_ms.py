"""Device milliseconds of edge generation (pipeline.generate_edges, core/rmat), per graph."""

MODULES = ('jit_generate_edges',)


def read(reading):
    seconds = reading.trace.module_s_per_unit(MODULES)
    return None if seconds is None else 1e3 * seconds
