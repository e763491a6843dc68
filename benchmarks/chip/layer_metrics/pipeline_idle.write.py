"""Share of the traced window in which the device sat idle under the
write pipeline's own host spans: idle seconds whose innermost host span
is an ``encode.*`` (fused finish, scalar gather, assembly), ``sharded.*``
or ``write.*`` (copy-in, dispatch, serialize, the write loop) span, over the
window.  Nothing where no idle gap carries such a label."""

PREFIXES = ("encode.", "sharded.", "write.")


def read(r):
    if r.trace is None or r.trace.n_devices == 0:
        return None
    idle = [s for label, s in r.trace.gaps_s.items()
            if label.startswith(PREFIXES)]
    if not idle:
        return None
    return 100.0 * sum(idle) / r.trace.window_s
