"""Share of the HBM roofline reached by the walk program
(data/walks.distributed_walks), in %.

The least time is the bytes a call must move at the least, at the chip's
HBM bandwidth (peaks.py), over its measured device time per call.  The
bytes follow from the call's sizes alone, whatever implements it: at each
of the L hops every one of the W walkers reads two offsets and one
adjacency entry, and the corpus of W x (L + 1) ids is written once.
"""

MODULES = ('jit_distributed_walks',)


def min_bytes(walks, id_bytes: int) -> int:
    W, L = walks.walkers, walks.length
    return id_bytes * (3 * W * L + W * (L + 1))


def read(reading):
    seconds = reading.trace.module_s_per_unit(MODULES)
    if seconds is None or reading.peak is None or reading.walks is None:
        return None
    least = min_bytes(reading.walks, reading.id_bytes) / reading.peak.hbm_bytes_per_s
    return 100.0 * least / seconds
