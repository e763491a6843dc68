"""Share of the traced window in which the device sat idle while the
lossless codec's host stages ran: idle seconds whose innermost host span
is a ``codec.*`` span (``lossless_batch``: ``codec.encode`` and the host
stages in it, stats, select, pack, payload, segments), over the window.  Nothing where no idle gap
carries such a label, as in a program that opens no such span."""

PREFIXES = ("codec.",)


def read(r):
    if r.trace is None or r.trace.n_devices == 0:
        return None
    idle = [s for label, s in r.trace.gaps_s.items()
            if label.startswith(PREFIXES)]
    if not idle:
        return None
    return 100.0 * sum(idle) / r.trace.window_s
