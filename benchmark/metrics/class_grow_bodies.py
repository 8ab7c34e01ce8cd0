"""Grower bodies the window's fits' chunk program holds: the ``train.fit``
spans' ``grow_bodies`` (how many times the grower was traced into the
program, or, where the program came from a cache, the number recorded
when it was built; the same in every fit).  None where the program
does not say."""

from benchmark.metrics.efb_bundle_columns import same_in_every_fit


def read(run):
    return same_in_every_fit(run, "grow_bodies")
