"""How near each chip's sends come to the ICI peak: the bytes a chip
sends per exchange (three payloads, each padded to 128 B per buffer)
over the device time of that exchange's collectives (the union of the
collective operations on the chip, per exchange in the traced
stretch), over the peak send rate of ``bench/peaks.json`` (1,600
Gbit/s). Only sends are counted, so the share cannot pass 100% whether
the published figure is per direction or for both."""


def read(run):
    t, s = run.trace, run.system
    if t is None or t.collective_s <= 0:
        return None
    n = sum(1 for a, b in s.exchanges if run.tracer.covers(a, b))
    if not n:
        return None
    per_exchange = t.collective_s / n
    return (100.0 * s.bytes_sent_per_chip / per_exchange
            / run.peak["ici_send_bytes_per_s"])
