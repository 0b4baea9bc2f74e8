"""Seconds from the start of the process to the first timed request:
imports, weights, program build, provisioning, warm-up and the ramp."""


def read(run):
    return run.setup_s
