"""Share of the traced stretch in which no operation ran, averaged over
the four chips:
1 - busy / window, busy being the union of the ``XLA Ops`` events."""


def read(run):
    t = run.trace
    if t is None or t.devices == 0 or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
