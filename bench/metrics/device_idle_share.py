"""One minus the union of the device's operation intervals over the
traced window, in percent."""


def read(r):
    if r.reduced is None or r.reduced.busy_s <= 0:
        return None
    return 100.0 * r.reduced.idle_share
