"""Share of the traced window in which no operation ran on the chip (%)."""


def read(r):
    share = r.trace.idle_share() if r.trace is not None else None
    return None if share is None else share * 100.0
