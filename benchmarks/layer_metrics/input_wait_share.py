"""The share of the window the loop spent inside the harness's own reader
and feeder calls (host spans, summed): the input path's blocking share."""


def read(run, args):
    names = args.get("spans", ["reader", "feeder"])
    if not run["window_s"] or not any(n in run["spans"] for n in names):
        return None
    waited = sum(run["spans"].get(n, 0.0) for n in names)
    return 100.0 * waited / run["window_s"]
