"""Share of the traced stretch of the window in which no operation ran on
the device: one less the union of the device's operation intervals over
the stretch's length, averaged over the chips."""


def read(record):
    tr = record.get("trace")
    return None if tr is None else 100.0 * tr["idle_share"]
