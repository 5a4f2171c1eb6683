"""Fixpoint rounds of the latest-departure group per advance: the mean of
its entry of ``SweepState.last_rounds`` over the window's advances (the
``ld_rounds`` counter of the journeys client)."""


def read(rec):
    return rec.mean("ld_rounds")
