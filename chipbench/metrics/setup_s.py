"""Seconds from the harness's start to the window's opening: JAX start-up,
weights, the gateway's build and tick compile (or cache read), the warm
round through HTTP and the load's ramp."""


def read(run):
    return run.setup_s
