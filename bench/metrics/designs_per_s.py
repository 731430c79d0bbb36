"""designs_per_s: designs completed in the window over the window's span.

A closed loop starts the window with every lane idle, so its span ends at
the last completion inside ``--seconds``: the work and the time both stop
there, and the rate carries no partial wave."""


def read(ctx):
    if not ctx.records:
        return None
    return len(ctx.records) / ctx.window_s
