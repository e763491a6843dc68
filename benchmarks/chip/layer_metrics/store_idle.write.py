"""Share of the traced window in which the device sat idle under the store
writer's host spans: idle seconds whose innermost host span is a
``store.*`` span (``store.write`` outside the pipeline, ``store.entry``,
``store.segment_close``, ``store.finalize``), over the window.  Nothing
where no idle gap carries such a label, as in a program that opens no
such span."""

PREFIXES = ("store.",)


def read(r):
    if r.trace is None or r.trace.n_devices == 0:
        return None
    idle = [s for label, s in r.trace.gaps_s.items()
            if label.startswith(PREFIXES)]
    if not idle:
        return None
    return 100.0 * sum(idle) / r.trace.window_s
