"""Share of the host schedule rounds built in the window that its calls
used, in percent. The fault plan and the mobility trace are generated
from round 0 on every call and sliced to the call's rounds, so a call
that starts at round s builds s + R rounds of each to use R: the
program's ``/cdfl/<faults|mobility>/rounds_used`` over
``.../rounds_built``, summed over the window."""
from benchlib import counters

SCALARS = counters.listen()
KINDS = ("faults", "mobility")


def read(ctx):
    lo, hi = ctx.window()
    built = sum(SCALARS.total(f"/cdfl/{k}/rounds_built", lo, hi)
                for k in KINDS)
    used = sum(SCALARS.total(f"/cdfl/{k}/rounds_used", lo, hi)
               for k in KINDS)
    if built <= 0:
        return None
    return 100.0 * used / built
