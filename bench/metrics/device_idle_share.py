"""Share of the traced window in which no operation ran on the chip:
100 * (1 - union of device-operation intervals / window).  Read for
``device_idle_share.serve`` and ``device_idle_share.legion`` alike; the
suffix names the end-to-end metric the share moves."""


def read(view):
    if view.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - view.trace.busy_s / view.trace.window_s)
