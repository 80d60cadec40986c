"""Image server: mean wait, after the step is queued, until the input
batch has landed on the device (``h2d_wait_s`` of the program's
``predict`` spans) over the window, in ms."""
import phases


def read(run):
    return phases.mean_ms(run, "h2d_wait_s")
