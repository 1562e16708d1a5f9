"""Median host time from the end of a transfer's successful attempt body
to ``run()`` returning: the store publish
(``api/mvhandle`` commit -> ``mvstore.mv_commit_fused``) as the client sees it."""
from measure import percentile


def read(rec):
    tr = rec.transfers
    return percentile((tr["t_end"] - tr["t_body_end"]) * 1e3, 50)
