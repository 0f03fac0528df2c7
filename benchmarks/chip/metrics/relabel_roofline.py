"""Share of the HBM roofline reached by the relabel phase (core/relabel), in %.

The least time is the bytes the phase must move at the least, at the
chip's HBM bandwidth (peaks.py), over its measured device time per graph.
The bytes follow from the graph's sizes alone, whatever implements the
phase: read src and dst (2m ids) and pv (n ids) once, write the relabeled
src and dst (2m ids).
"""

MODULES = ('jit_relabel_ring', 'jit_relabel_recompute')


def min_bytes(graph, id_bytes: int) -> int:
    return id_bytes * (4 * graph.m + graph.n)


def read(reading):
    seconds = reading.trace.module_s_per_unit(MODULES)
    if seconds is None or reading.peak is None:
        return None
    least = min_bytes(reading.graph, reading.id_bytes) / reading.peak.hbm_bytes_per_s
    return 100.0 * least / seconds
