"""Share of the traced stretch in which a run of a program was open on
the device and no operation ran: the part of ``device_idle.train`` inside
the program, apart from the gaps between runs, which are the host's
(``bench/scopes.py``)."""
from bench import scopes


def read(record):
    r = scopes.reduce(record)
    return None if r is None else 100.0 * r["idle_within_run"]
