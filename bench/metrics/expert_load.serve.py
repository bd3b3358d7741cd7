"""Tokens per decode step at the busiest held expert over the mean of the
held experts, averaged over the MoE layers: from the ``load`` counter
that the decode step keeps in its cache (tokens each held expert took),
read at the window's start and end."""


def read(record):
    return record["window"].get("expert_load")
