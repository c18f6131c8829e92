"""Share of the slots the Phase-2 ladder gather reads that hold a real
bin, in %: the built index's ``gather_fill_pct`` (real bins over the
slots of the segmented row layout the kernel pour gathers from). Nothing
where the index has no such layout."""


def read(rec):
    return getattr(rec.run.index, "gather_fill_pct", None)
