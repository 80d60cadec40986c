"""Model step: the configuration's int8 operations per image (2 x MACs,
counted from its shapes) times the images answered per second over the
traced stretch, over the chip's int8 peak, in %."""
import workcount


def read(run):
    t = run.trace
    if not t or not t["images"]:
        return None
    rate = t["images"] / t["host_s"]
    return 100.0 * workcount.ops_per_image(run.layers) * rate \
        / run.peak["int8_ops_per_s"]
