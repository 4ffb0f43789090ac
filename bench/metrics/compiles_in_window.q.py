"""Backend compilations and persistent-cache loads inside the window (the
programs' names are in the result's notes, ``compiled_in_window``)."""


def read(run):
    return float(len(run.compiles))
