"""Fixpoint rounds of the earliest-arrival group per advance: the mean of
``SweepState.last_rounds`` over the window's advances."""


def read(rec):
    return rec.mean("ea_rounds")
