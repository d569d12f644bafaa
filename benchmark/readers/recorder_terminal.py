"""Share, in %, of the spans begun in the window that ended with the
terminal mark ``args.terminal`` (``dropped@submit``: a capture the encode
pipeline had no room for)."""


def read(run, args):
    if not run.spans:
        return None
    n = sum(1 for tr in run.spans if tr.terminal == args["terminal"])
    return 100.0 * n / len(run.spans)
