"""Output tokens emitted in the window, over the window."""


def read(run):
    n = sum(1 for r in run.recs if r.section != "warm0"
            for t in r.times if run.in_window(t))
    return n / run.window_s
