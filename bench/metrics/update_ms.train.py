"""The SAC updates of one training iteration: the window's mean iteration
time less ``collect_ms.train``."""


def read(record):
    w = record["window"]
    if "iteration_s" not in w or "collect_s" not in w:
        return None
    return (w["iteration_s"] - w["collect_s"]) * 1e3
