"""Image server: mean time to slice the output and copy it to the host
(``fetch_s`` of the program's ``predict`` spans) over the window, in
ms."""
import phases


def read(run):
    return phases.mean_ms(run, "fetch_s")
