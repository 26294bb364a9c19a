"""How unevenly the router spread the window's work: (most - fewest) /
mean of the requests each replica completed in the window, percent.
``None`` with one replica, or where nothing completed."""


def read(ctx):
    done = ctx["completed"]
    if len(done) < 2 or sum(done) <= 0:
        return None
    return 100.0 * (max(done) - min(done)) * len(done) / sum(done)
