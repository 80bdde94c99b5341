"""fragment compile: programs the process-global compile service built
between the window's start and its end (SERVICE.stats()["builds"]).
Expected 0: every shape was warmed in set-up."""


def read(ctx):
    return float(ctx["builds_in_window"])
