"""Host microseconds of one Legion tile call: each ``legion.tile`` span's
length less the device-busy time inside it (the kernel's dispatch, the
host's slicing and packing, and the device-to-host copy's wait), the mean
over the tile calls of the traced window.  The device is busy about 1 µs
of a call's 1,200, so the offset of the device planes' clock against the
host's moves the reading by at most that."""
from bench import program_trace

TILE = "legion.tile"


def read(view):
    r = program_trace.of(view)
    tiles = r.spans_named(TILE) if r is not None else []
    if not tiles:
        return None
    host = sum((t.end - t.start) * 1e-9 - b
               for t, b in zip(tiles, r.busy_in_each(tiles)))
    return 1e6 * host / len(tiles)
