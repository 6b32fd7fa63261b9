"""Share of the rows the session dispatched that were bucket padding:
``SearcherStats.padded_rows`` over padded plus answered rows, both
counted over the window, in %."""


def read(ctx):
    padded = ctx["counters"].get("padded_rows")
    rows = len(ctx["window"].qidx)
    if padded is None or not rows:
        return None
    return 100.0 * padded / (padded + rows)
