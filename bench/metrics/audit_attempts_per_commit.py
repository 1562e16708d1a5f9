"""Attempts per committed audit in the window: body calls made by the
system's retry loop (``repro.api.run``) over audits it committed."""


def read(rec):
    if not rec.audits:
        return None
    return sum(a["attempts"] for a in rec.audits) / len(rec.audits)
