"""Requests completed per second: every request offered in the window,
over the time from the window's start to the last completion."""


def read(run):
    return run["completed"] / run["span_s"] if run["completed"] else None
