"""Process start to the end of the call that opens the window: JAX and
device start-up, the cell's own set-up (a node started, or a chain built
or loaded), the cell's device programs compiled or loaded, and the
warm-up."""


def read(run):
    return run.setup_s
