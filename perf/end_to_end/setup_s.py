"""Set-up time: process start to the first timed call.

It holds importing JAX, reaching the chips, generating the initial
states, compiling or loading every program from the compile cache, and
one execution of each call of the schedule.
"""


def read(run):
    return run.setup_s
