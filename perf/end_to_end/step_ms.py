"""Milliseconds per model step over the whole measured window.

The window's seconds over every model step of the whole calls completed
in it: bootstraps, the start of each solve and the host's wait for each
call's result are all inside.  It is the reference benchmark's steps per
second, inverted; a solve's time to solution is this times its steps.
"""


def read(run):
    return run.window.seconds / run.window.steps * 1e3
