"""Share of the traced stretch in which no operation ran on the device,
averaged over the devices used."""
from perf import trace as tracing


def read(ctx):
    t = ctx["trace"]
    if t is None or not any(t.ops):
        return None
    window = t.window()
    shares = [tracing.idle_share(dev, window) for dev in t.ops if dev]
    return 100.0 * sum(shares) / len(shares)
