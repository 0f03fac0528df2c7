"""Share of the HBM roofline reached by the redistribute phase
(core/redistribute, with capacity_all_to_all), in %.

The least time is the bytes the phase must move at the least, at the
chip's HBM bandwidth (peaks.py), over its measured device time per graph.
The bytes follow from the graph's sizes alone, whatever implements the
phase: read src and dst (2m ids) once, write the owned src and dst (2m ids).
"""

MODULES = ('jit_redistribute_sorted',)


def min_bytes(graph, id_bytes: int) -> int:
    return id_bytes * (4 * graph.m)


def read(reading):
    seconds = reading.trace.module_s_per_unit(MODULES)
    if seconds is None or reading.peak is None:
        return None
    least = min_bytes(reading.graph, reading.id_bytes) / reading.peak.hbm_bytes_per_s
    return 100.0 * least / seconds
