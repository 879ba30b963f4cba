"""Seconds from the start of the process to the start of the window:
imports, weights, compiles or compile-cache loads, warm groups and the
warm phase of the cell's traffic."""


def read(run):
    return run.setup_s
