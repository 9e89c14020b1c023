"""Requests decided per flush of the admission queue."""


def read(r):
    flushes = r.extra.get("flushes")
    return r.extra["decided"] / flushes if flushes else None
