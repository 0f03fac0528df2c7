"""Device milliseconds of the CSR build (core/csr), per graph."""

MODULES = ('jit_build_csr_sorted',)


def read(reading):
    seconds = reading.trace.module_s_per_unit(MODULES)
    return None if seconds is None else 1e3 * seconds
