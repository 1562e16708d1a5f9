"""Attempts per committed transfer in the window: body calls made by the
system's retry loop (``repro.api.run``) over transfers it committed."""


def read(rec):
    attempts = rec.transfers["attempts"]
    if attempts.size == 0:
        return None
    return float(attempts.sum()) / float(attempts.size)
