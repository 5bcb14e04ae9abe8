"""Shuffle dispatch: how unevenly the window's shuffles load the
workers, the rows-weighted mean over the ``shuffle.dispatch`` spans of
``max_worker_rows`` over the mean rows per worker (1 is even; the
hubs of a power-law graph raise it)."""


def read(run):
    spans = [s for s in run.spans_named("shuffle.dispatch")
             if "max_worker_rows" in s.args and s.args.get("rows")]
    if not spans:
        return None          # a program without the argument
    rows = sum(int(s.args["rows"]) for s in spans)
    return sum(int(s.args["m"]) * int(s.args["max_worker_rows"])
               for s in spans) / rows
