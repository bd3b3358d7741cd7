"""Time of one training iteration that skips the SAC update: the collect
alone.  Host clock over the replay warm-up's iterations after the first,
timed together and divided by their number."""


def read(record):
    s = record["window"].get("collect_s")
    return None if s is None else s * 1e3
